"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's CI strategy of running the whole suite under
``mpirun -n 1…8`` (reference ``Jenkinsfile:24-33``): multi-*device* on one
host is the proxy for multi-chip, via
``--xla_force_host_platform_device_count`` (SURVEY.md §4).

Must run before jax initializes a backend: the platform and the device-count
flag are set here, ahead of the jax import. Nothing else is needed to run the
suite on the CPU (``JAX_PLATFORMS=cpu python -m pytest tests/``).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    # HEAT_TPU_TEST_DEVICES drives the reference-style device ladder
    # (mpirun -n 1…8 → suite runs at 1/2/4/8 virtual devices,
    # scripts/run_suite_ladder.sh)
    ndev = os.environ.get("HEAT_TPU_TEST_DEVICES", "8")
    os.environ["XLA_FLAGS"] = flags + f" --xla_force_host_platform_device_count={ndev}"

import jax  # noqa: E402

if jax.default_backend() != "cpu":
    raise RuntimeError(
        "tests require a virtual CPU mesh; run with JAX_PLATFORMS=cpu "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8 python -m pytest tests/"
    )
# Like the reference's `mpirun -n 1…8` CI ladder, the suite runs at ANY
# device count (1, 2, 4, 8, …): tests read the size from the communicator
# rather than assuming 8.

# Persistent XLA compilation cache: OFF unless JAX_COMPILATION_CACHE_DIR is
# set from outside (jax reads that variable itself; nothing is set in code).
# It stays off by default for CPU test runs because reloading XLA:CPU AOT
# executables on a shared host is unsound: the loader logs machine-feature
# mismatches ("+prefer-no-scatter … could lead to execution errors such as
# SIGILL") and warm-cache runs reproducibly died with "Fatal Python error:
# Aborted" inside a deserialized executable (test_transformer remat,
# 2026-08-01 — twice, while cold runs pass). Wall-clock comes from
# pytest-xdist file-level parallelism instead (``-n 6 --dist loadfile``;
# loadfile keeps each module's shared-rng draw order intact).
# Per-test executable/counter log for the ladder (the per-process executable budget): when
# HEAT_TPU_LADDER_STATS names a file, append one JSON line after every test
# with the accumulated live-array count (the jit-executable growth proxy)
# and the framework's compile counters. Written line-by-line with flush, so
# on a SIGABRT the last line is the state right before the abort —
# run_suite_ladder.py persists it next to abort_traceback.
_LADDER_STATS = os.environ.get("HEAT_TPU_LADDER_STATS", "")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long soak tests excluded from tier-1; run with "
        "HEAT_TPU_RUN_SLOW=1 (the suite ladder sets it)")


def pytest_collection_modifyitems(config, items):
    # tier-1 stays bounded: the plain suite skips soak tests; the ladder's
    # full runs opt in via HEAT_TPU_RUN_SLOW=1 ("0"/"false" stay off, same
    # convention as HEAT_TPU_NATIVE)
    if os.environ.get("HEAT_TPU_RUN_SLOW", "") not in ("", "0", "false",
                                                       "False"):
        return
    skip = pytest.mark.skip(reason="slow soak; set HEAT_TPU_RUN_SLOW=1")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


def pytest_runtest_teardown(item, nextitem):
    if not _LADDER_STATS:
        return
    try:
        import json

        from heat_tpu.utils import metrics as _metrics

        c = _metrics.counters()
        rec = {
            "test": item.nodeid,
            "live_arrays": len(jax.live_arrays()),
            "plan_misses": int(c.get("resharding.plan_misses", 0)),
            "serve_program_compiles": int(c.get("serve.program_compiles", 0)),
            "align_resplits": int(c.get("op_engine.align_resplits", 0)),
            # fusion engine: flush volume + program-cache growth ride next
            # to the executable counters (executable budget — fusion should LOWER
            # the accumulated executable count; log it so the SIGABRT
            # correlation data improves)
            "fusion_flushes": int(c.get("op_engine.fusion_flushes", 0)),
            "fusion_reduce_flushes": int(
                c.get("op_engine.fusion_reduce_flushes", 0)),
            "fusion_contract_flushes": int(
                c.get("op_engine.fusion_contract_flushes", 0)),
            "fusion_resplit_nodes": int(
                c.get("op_engine.fusion_resplit_nodes", 0)),
            "fusion_resplit_fallbacks": int(
                c.get("op_engine.fusion_resplit_fallbacks", 0)),
            "fusion_step_flushes": int(
                c.get("op_engine.fusion_step_flushes", 0)),
            "fusion_step_fallbacks": int(
                c.get("op_engine.fusion_step_fallbacks", 0)),
            # tape-compiled analytics fit steps (the FIT=0/1 ladder A/B
            # reads these: which tests dispatched compiled estimator
            # iterations, and whether any degraded to the eager loop)
            "fit_step_flushes": int(
                c.get("op_engine.fit_step_flushes", 0)),
            "fit_step_fallbacks": int(
                c.get("op_engine.fit_step_fallbacks", 0)),
            # quantized packed collectives: which tests actually moved
            # quantized bytes (the QUANT=0/1 ladder A/B reads these)
            "quant_collectives": int(
                c.get("op_engine.quant_collectives", 0)),
            "quant_bytes_saved": int(
                c.get("op_engine.quant_bytes_saved", 0)),
            # chunk-pipelined packed collectives (the CHUNKS=1/4 ladder
            # A/B reads these: which tests dispatched chunked legs, and
            # whether any chunk plan degraded to the unchunked program)
            "chunk_collectives": int(
                c.get("op_engine.chunk_collectives", 0)),
            "chunk_fallbacks": int(
                c.get("op_engine.chunk_fallbacks", 0)),
            # tier-aware hierarchical packed collectives (the HIER=0/1
            # ladder A/B reads these: which tests decomposed payload
            # groups, and whether any hier plan degraded to flat)
            "hier_collectives": int(
                c.get("op_engine.hier_collectives", 0)),
            "hier_fallbacks": int(
                c.get("op_engine.hier_fallbacks", 0)),
            # continuous-batching decode engine (the --decode-smoke
            # ladder stage reads these: which tests dispatched slot
            # steps, and whether any degraded to the uncompiled step)
            "serve_decode_steps": int(c.get("serve.decode_steps", 0)),
            "serve_decode_fallbacks": int(
                c.get("serve.decode_fallbacks", 0)),
            # tape-compiled data engine (the --data-smoke ladder stage
            # reads these: which tests dispatched compiled exchange /
            # carry-fold programs, and whether any degraded to eager)
            "data_engine_dispatches": int(
                c.get("data_engine.dispatches", 0)),
            "data_engine_exchange_fallbacks": int(
                c.get("data_engine.exchange_fallbacks", 0)),
            "data_engine_stream_chunks": int(
                c.get("data_engine.stream_chunks", 0)),
            "data_engine_stream_fallbacks": int(
                c.get("data_engine.stream_fallbacks", 0)),
            "zero_fills": int(c.get("op_engine.zero_fills", 0)),
            "fusion_ops": int(c.get("op_engine.fusion_ops", 0)),
            "fusion_program_compiles": int(
                c.get("fusion.program_compiles", 0)),
            "fusion_program_hits": int(c.get("fusion.program_hits", 0)),
        }
        with open(_LADDER_STATS, "a") as f:
            f.write(json.dumps(rec) + "\n")
            f.flush()
    except Exception:  # the log must never fail a test run
        pass
