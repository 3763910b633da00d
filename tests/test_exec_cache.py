"""Executable-cache contracts (`repro.core.execache`).

Two layers of guarantees:

* unit: wrap() keys on (name, backend, statics, shapes/dtypes), reuses
  the in-process registry, round-trips executables through the disk
  directory into a FRESH process (the serialization must be portable —
  a regression here is the "Symbols not found" class of failure where
  an executable loads in the process that wrote it but nowhere else),
  and falls back to plain jit under tracers / ZKDL_EXEC_MODE=off;
* integration: the cross-process warm-start contract — process A
  compiles + proves, process B reconstructs the ProvingKey for the same
  config and proves WITHOUT re-tracing or re-compiling a single wrapped
  program (``stats()["misses"] == 0``), and B's proof still verifies
  and matches the pinned golden bytes.  This is what makes a restarted
  prover service warm (tentpole of the depth/T-invariant compile work).
"""
import hashlib
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")


def _run_child(code: str, cache_dir: str, xla_flags: str | None = None
               ) -> dict:
    """Run ``code`` in a fresh interpreter with the exec cache pointed
    at ``cache_dir`` (and ``XLA_FLAGS`` set to ``xla_flags`` when given);
    the child must print one JSON object on stdout."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["ZKDL_EXEC_CACHE"] = cache_dir
    if xla_flags is not None:
        env["XLA_FLAGS"] = xla_flags
    env.pop("ZKDL_EXEC_MODE", None)
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=1200)
    assert proc.returncode == 0, \
        f"child failed:\n{proc.stdout[-1000:]}\n{proc.stderr[-3000:]}"
    return json.loads(proc.stdout.splitlines()[-1])


# ---------------------------------------------------------------------------
# Unit: registry, keys, fallbacks
# ---------------------------------------------------------------------------

def test_registry_hit_and_stats(monkeypatch, tmp_path):
    import jax.numpy as jnp
    from repro.core import execache

    monkeypatch.setenv("ZKDL_EXEC_CACHE", str(tmp_path))
    fn = execache.wrap("t_add1", lambda x: x + 1)
    execache.reset_stats()
    x = jnp.arange(8, dtype=jnp.int32)
    np.testing.assert_array_equal(np.asarray(fn(x)), np.arange(1, 9))
    s1 = execache.stats()
    assert s1["misses"] == 1 and s1["disk_writes"] == 1
    fn(x)                                   # same shape: registry hit
    s2 = execache.stats()
    assert s2["hits"] == s1["hits"] + 1 and s2["misses"] == 1
    fn(jnp.arange(16, dtype=jnp.int32))     # new shape: new executable
    assert execache.stats()["misses"] == 2


def test_static_args_partition_the_key(monkeypatch, tmp_path):
    import jax.numpy as jnp
    from repro.core import execache

    monkeypatch.setenv("ZKDL_EXEC_CACHE", str(tmp_path))
    fn = execache.wrap("t_scale", lambda x, k: x * k,
                       static_argnames=("k",))
    execache.reset_stats()
    x = jnp.arange(4, dtype=jnp.int32)
    np.testing.assert_array_equal(np.asarray(fn(x, k=2)), [0, 2, 4, 6])
    np.testing.assert_array_equal(np.asarray(fn(x, k=3)), [0, 3, 6, 9])
    assert execache.stats()["misses"] == 2  # distinct statics, two exes


def test_disabled_mode_falls_back_to_jit(monkeypatch):
    import jax.numpy as jnp
    from repro.core import execache

    monkeypatch.setenv("ZKDL_EXEC_MODE", "off")
    fn = execache.wrap("t_off", lambda x: x * 2)
    execache.reset_stats()
    np.testing.assert_array_equal(
        np.asarray(fn(jnp.arange(4, dtype=jnp.int32))), [0, 2, 4, 6])
    assert execache.stats() == {"hits": 0, "misses": 0, "disk_hits": 0,
                                "disk_writes": 0, "disk_corrupt": 0}


def test_corrupt_disk_entry_is_a_miss_not_a_crash(monkeypatch, tmp_path):
    """PR 8 robustness contract: a truncated/corrupt serialized
    executable (crashed writer, bit rot, the chaos harness's
    ``corrupt-cache`` fault) is treated as a MISS — counted, the bad
    file dropped, the program recompiled and REWRITTEN so the next cold
    start loads warm again."""
    import jax.numpy as jnp
    from repro.core import execache

    monkeypatch.setenv("ZKDL_EXEC_CACHE", str(tmp_path))
    fn = execache.wrap("t_corrupt", lambda x: x - 3)
    execache.reset_stats()
    x = jnp.arange(6, dtype=jnp.int32)
    np.testing.assert_array_equal(np.asarray(fn(x)), np.arange(-3, 3))
    assert execache.stats()["disk_writes"] == 1
    entries = [f for f in os.listdir(execache.cache_dir())
               if f.endswith(".exe.pkl")]
    assert len(entries) == 1
    path = os.path.join(execache.cache_dir(), entries[0])
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 2)

    execache.clear()                    # force the disk-load path
    execache.reset_stats()
    np.testing.assert_array_equal(np.asarray(fn(x)), np.arange(-3, 3))
    s = execache.stats()
    assert s["disk_corrupt"] == 1 and s["misses"] == 1 \
        and s["disk_hits"] == 0 and s["disk_writes"] == 1, s

    execache.clear()                    # rewritten entry must load clean
    execache.reset_stats()
    np.testing.assert_array_equal(np.asarray(fn(x)), np.arange(-3, 3))
    s = execache.stats()
    assert s["disk_hits"] == 1 and s["misses"] == 0 \
        and s["disk_corrupt"] == 0, s


def test_tracer_args_inline_into_outer_jit(monkeypatch, tmp_path):
    """A wrapped function traced inside another jitted program must
    inline (a Compiled can't consume tracers) and still be correct."""
    import jax
    import jax.numpy as jnp
    from repro.core import execache

    monkeypatch.setenv("ZKDL_EXEC_CACHE", str(tmp_path))
    inner = execache.wrap("t_inner", lambda x: x + 5)

    @jax.jit
    def outer(x):
        return inner(x) * 2

    np.testing.assert_array_equal(
        np.asarray(outer(jnp.arange(3, dtype=jnp.int32))), [10, 12, 14])


def test_disk_roundtrip_into_fresh_process(tmp_path):
    """An executable serialized by one process must load and RUN in a
    different process: write in child A, consume in child B with zero
    misses.  Catches non-portable serializations (e.g. executables that
    came out of the XLA persistent cache carry no object code)."""
    code = """
    import json
    import numpy as np
    import jax.numpy as jnp
    from repro.core import execache
    fn = execache.wrap("t_xproc", lambda x: (x * x + 1).sum())
    execache.reset_stats()
    out = int(fn(jnp.arange(32, dtype=jnp.int64)))
    print(json.dumps({"out": out, "stats": execache.stats()}))
    """
    a = _run_child(code, str(tmp_path))
    want = int(sum(i * i + 1 for i in range(32)))
    assert a["out"] == want
    assert a["stats"]["misses"] == 1 and a["stats"]["disk_writes"] == 1
    b = _run_child(code, str(tmp_path))
    assert b["out"] == want
    assert b["stats"]["misses"] == 0, \
        f"fresh process re-compiled despite populated disk: {b['stats']}"
    assert b["stats"]["disk_hits"] == 1


def test_disk_entry_loads_in_multi_device_process(tmp_path):
    """A wrapped program is a single-device jit: written by a one-device
    process, it must load and run in a process with several devices
    (forced host devices), not expect one argument shard per device."""
    code = """
    import json
    import jax
    import jax.numpy as jnp
    from repro.core import execache
    fn = execache.wrap("t_multidev", lambda x: x * 3 + 1)
    execache.reset_stats()
    out = [int(v) for v in fn(jnp.arange(4, dtype=jnp.int32))]
    print(json.dumps({"out": out, "n_dev": len(jax.devices()),
                      "stats": execache.stats()}))
    """
    a = _run_child(code, str(tmp_path), xla_flags="")
    assert a["n_dev"] == 1 and a["stats"]["disk_writes"] == 1
    b = _run_child(code, str(tmp_path),
                   xla_flags="--xla_force_host_platform_device_count=4")
    assert b["n_dev"] == 4
    assert b["out"] == [1, 4, 7, 10]
    assert b["stats"]["disk_hits"] == 1 and b["stats"]["misses"] == 0


# ---------------------------------------------------------------------------
# Integration: cross-process warm prover start
# ---------------------------------------------------------------------------

# the golden byte digest pinned in tests/test_proofio.py for the seed-7
# uniform T=1 trajectory — process B must reproduce it from a cold start
GOLDEN_SHA256_T1 = \
    "a538160f1da619bd39439420f78d24af9089dd1eacd770f3ce24d76dd80c2422"

_PROVE_CHILD = """
import hashlib, json
import numpy as np
from repro.core import execache
from repro.core.quantfc import QuantConfig, synthetic_sgd_trajectory
from repro.core.pipeline import (PipelineConfig, encode_proof, make_keys,
                                 prove_session, verify_session)
cfg = PipelineConfig(n_layers=2, batch=2, width=4, q_bits=16, r_bits=4,
                     n_steps=1)
keys = make_keys(cfg)
wits = synthetic_sgd_trajectory(1, 2, 2, 4,
                                QuantConfig(q_bits=16, r_bits=4), seed=7)
execache.reset_stats()
proof = prove_session(keys, wits, np.random.default_rng(7))
print(json.dumps({
    "stats": execache.stats(),
    "sha": hashlib.sha256(encode_proof(proof)).hexdigest(),
    "verified": bool(verify_session(keys, proof)),
}))
"""


def test_cross_process_warm_start():
    """Process B (a fresh interpreter) reconstructs the ProvingKey for a
    config process A already proved and proves WITHOUT a single
    executable-cache miss — no re-trace, no re-lower, no re-compile of
    any wrapped program — and its proof verifies and matches the golden
    bytes.  Uses the session's real cache directory (default or
    $ZKDL_EXEC_CACHE): populating it is process A's job, and the suite
    itself plays process A on a genuinely cold machine."""
    from repro.core import execache

    if not (execache.enabled() and execache.cache_dir() is not None):
        pytest.skip("executable disk cache disabled in this environment")
    env_dir = execache.disk_root()

    # process A: prove once (fills any disk gaps for this geometry)
    a = _run_child(_PROVE_CHILD, env_dir)
    assert a["verified"] and a["sha"] == GOLDEN_SHA256_T1

    # process B: fresh interpreter, same config — must start warm
    b = _run_child(_PROVE_CHILD, env_dir)
    assert b["stats"]["misses"] == 0, (
        f"fresh process re-traced {b['stats']['misses']} programs "
        f"(warm-start contract broken): {b['stats']}")
    assert b["stats"]["disk_hits"] > 0
    assert b["verified"], "warm-started proof rejected"
    assert b["sha"] == GOLDEN_SHA256_T1, \
        "warm-started proof bytes diverge from the golden digest"
