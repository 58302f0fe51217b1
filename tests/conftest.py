"""Test config: force an 8-device virtual CPU platform BEFORE jax initializes.

SURVEY.md §4: the reference conformance-tests device backends by re-targeting
one harness per place; here the CPU platform with
--xla_force_host_platform_device_count=8 is the fake multi-chip fixture that
exercises the same shard_map/pjit code paths as a real TPU slice.
"""
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
# the fixture IS the CPU platform: hold jax to it whatever the machine has
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

from paddle_tpu.core.compile_cache import use_compile_cache  # noqa: E402

# persistent compilation cache: repeat suite runs skip XLA recompiles
# (reference quarantines slow tests via tools/parallel_UT_rule.py; our
# equivalent is @pytest.mark.slow + this cache)
use_compile_cache()
# on top of its compile-time threshold, persist ONLY the jitted step
# programs (hapi
# train/eval steps, the serving unified step) — the entries whose
# mid-process deserialization has years of green runs behind it.
# Eager primitives (most of all the per-call lax.scan of an eager
# gpt_forward: each call builds a fresh body closure -> fresh jaxpr ->
# in-memory cache miss -> disk read) must NOT be persisted: XLA:CPU's
# deserialize_executable reproducibly segfaults on those reads late in
# a long suite in this environment (same machine-feature problem
# family as the AOT-blob note below).  Recompiling them costs
# milliseconds per test; deserializing them kills the whole run.

# best-effort: jax._src.compilation_cache.put_executable_and_time is a
# PRIVATE symbol and the "jit_step"/"jit__step" module naming is a jit
# convention — both can move under a jax upgrade.  If either is gone,
# fall back to stock persistent caching (slower repeat runs, nothing
# broken) instead of failing collection.
try:
    from jax._src import compilation_cache as _cc  # noqa: E402

    _orig_put = _cc.put_executable_and_time
except (ImportError, AttributeError):
    _cc = None

if _cc is not None:

    def _selective_put(*args, **kwargs):
        module_name = kwargs.get(
            "module_name", args[1] if len(args) > 1 else None)
        if isinstance(module_name, str) and not module_name.startswith(
                ("jit_step", "jit__step")):
            return None   # eager primitive: never persist (see above)
        # step program — or an unrecognized signature, where the stock
        # behavior is the safe degradation
        return _orig_put(*args, **kwargs)

    _cc.put_executable_and_time = _selective_put
# keep XLA:CPU AOT blobs out of the cache: reloading them trips a
# machine-feature check (prefer-no-scatter/-gather) and spams stderr
jax.config.update("jax_persistent_cache_enable_xla_caches", "none")

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed_everything():
    np.random.seed(1234)
    import paddle_tpu

    paddle_tpu.seed(1234)
    yield


@pytest.fixture
def in_fresh_process():
    """``call(test_file, function)``: the named function of a test module
    (loaded by path, as no test session is) run in a fresh interpreter,
    and what it returned, through JSON.  For host-time measurements that a
    mid-suite interpreter's daemon threads would inflate."""
    import json
    import subprocess
    import sys

    code = ("import importlib.util, json, sys\n"
            "spec = importlib.util.spec_from_file_location("
            "'measured_mod', sys.argv[1])\n"
            "mod = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(mod)\n"
            "print(json.dumps(getattr(mod, sys.argv[2])()))\n")

    def call(test_file, function):
        test_file = os.path.abspath(test_file)
        proc = subprocess.run(
            [sys.executable, "-c", code, test_file, function],
            capture_output=True, text=True, timeout=300,
            cwd=os.path.dirname(os.path.dirname(test_file)),
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    return call
