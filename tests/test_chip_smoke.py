"""The chip smoke test's own parts, exercised on the CPU.

``chip_smoke.py`` runs only on a TPU; here its numpy reference is
checked against the filters' hash, its phases run at toy sizes with the
Pallas kernels interpreted, and its refusal to run without a TPU is
checked end to end.
"""

import importlib.util
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fingerprint import fingerprint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize(
    "q,r,seed", [(28, 16, 0), (18, 26, 0), (12, 32, 3), (10, 4, 1)]
)
def test_numpy_fingerprints_match_filter_hash(cs, q, r, seed):
    keys = cs.distinct_keys(seed, 4096)
    fq, fr = fingerprint(jnp.asarray(keys), q, r, seed)
    want = (np.asarray(fq).astype(np.uint64) << np.uint64(r)) | np.asarray(fr).astype(
        np.uint64
    )
    np.testing.assert_array_equal(cs.fingerprints(keys, q + r, seed), want)


def test_distinct_keys_are_distinct_and_seeded(cs):
    a = cs.distinct_keys(7, 1 << 16)
    assert a.dtype == np.uint32 and np.unique(a).size == a.size
    np.testing.assert_array_equal(a, cs.distinct_keys(7, 1 << 16))
    assert not np.array_equal(a, cs.distinct_keys(8, 1 << 16))


def test_member_is_sorted_array_membership(cs):
    stored = np.array([5, 1, 9, 9, 3], np.uint32)
    queries = np.array([9, 2, 5, 5, 7, 1, 0], np.uint32)
    got = cs.member(stored, queries)
    np.testing.assert_array_equal(got, np.isin(queries, stored))


def test_phases_pass_at_toy_size_with_interpreted_kernels(cs, monkeypatch):
    """The QF and cascade phases end to end.  p = 14 fingerprint bits
    make some deleted keys share a fingerprint with a kept one, so the
    delete check's exception path is exercised too."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    devices = jax.devices()[:1]
    cs.qf_phase(0, devices, q=10, r=4, n_ins=512, batch=128, nq=128, ndel=64)
    cs.cascade_phase(0, devices, ram_q=8, p=30, n_ins=1 << 14, batch=512, nq=512)


def test_sharded_phase_on_four_virtual_devices():
    code = f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import jax, chip_smoke as cs
        cs.sharded_phase(0, jax.devices()[:4], q=14, r=14, n_ins=1 << 12,
                         batch=1 << 10, nq=1 << 9, qbatch=1 << 8)
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
        cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "state sharded over devices [0, 1, 2, 3]" in out.stdout


def test_refuses_to_run_without_a_tpu(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = subprocess.run(
        [sys.executable, SMOKE],
        capture_output=True,
        text=True,
        env=env,
        timeout=300,
        cwd=REPO,
    )
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok": true' not in out.stdout
