"""A run whose timed path is broken underneath must come out not correct.

Each test drives a whole rehearsal run of a cell (set-up, window,
check) on the CPU at the tiny geometry, with the chip checks skipped
and one fault planted in the filter library's façade, and sees
``correct`` come out false.  One chip: no exchange between chips to
leave out.

    python3 -m pytest -q bench/tests/test_faults.py
"""

from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import rehearse  # noqa: E402

rehearse.cpu_env()
os.environ["REPRO_KERNEL_MODE"] = "xla"

import jax.numpy as jnp  # noqa: E402

import harness  # noqa: E402

sys.path.insert(0, os.path.join(harness.ROOT, "src"))
from repro import filters  # noqa: E402


def flip_one_answer(orig):
    def contains(cfg, s, k):
        out = orig(cfg, s, k)
        return out.at[0].set(~out[0])

    return "contains", contains


def half_the_answers(orig):
    def contains(cfg, s, k):
        half = k.shape[0] // 2
        out = orig(cfg, s, k[:half])
        return jnp.concatenate([out, jnp.zeros(k.shape[0] - half, bool)])

    return "contains", contains


def state_unchanged(orig):
    return "insert", lambda cfg, s, k, kk=None: s


def half_the_batch(orig):
    return "insert", lambda cfg, s, k, kk=None: orig(cfg, s, k, k.shape[0] // 2)


def keys_altered(orig):
    def insert(cfg, s, k, kk=None):
        flip = (jnp.arange(k.shape[0]) % 16 == 0).astype(k.dtype)
        return orig(cfg, s, k ^ flip, kk)

    return "insert", insert


FAULTS = {
    "ycsb_qf28.read_c": [flip_one_answer, half_the_answers],
    "ycsb_qf28.load": [state_unchanged, half_the_batch, keys_altered],
}
CASES = [(cell, f) for cell, fs in FAULTS.items() for f in fs]


def run(cell, seconds=1.0):
    result, _ = rehearse.rehearse(cell, 2**31 + 99, seconds, False)
    return result


@pytest.mark.parametrize("cell", sorted(FAULTS))
def test_sound_run_is_correct(cell):
    assert run(cell)["correct"]


@pytest.mark.parametrize("cell,fault", CASES, ids=[f"{c}-{f.__name__}" for c, f in CASES])
def test_fault_is_caught(cell, fault, monkeypatch):
    op = "contains" if fault in FAULTS["ycsb_qf28.read_c"] else "insert"
    name, broken = fault(getattr(filters, op))
    monkeypatch.setattr(filters, name, broken)
    result = run(cell)
    assert not result["correct"], result["checks"]
