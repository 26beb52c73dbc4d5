"""Test config: run on the CPU backend with 8 virtual devices.

Mirrors the reference's strategy of re-running suites on backend
variants (SURVEY §4.9): unit tests run on CPU with 8 virtual devices so
multi-chip sharding paths compile and execute without TPU hardware.
The chip itself is exercised by ``chip_smoke.py`` and by the
``chip``-marked tests, which need the real backend:
``PADDLE_TPU_CHIP_TESTS=1 python -m pytest -m chip`` leaves JAX on
the platform it finds; everywhere else those tests skip.
"""

import os

ON_CHIP = os.environ.get("PADDLE_TPU_CHIP_TESTS") == "1"

# Must be set before jax import.
if not ON_CHIP:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402
import jax  # noqa: E402

# Exact f32 matmuls for numeric checks. Not on the chip: training there
# keeps the default MXU precision, Mosaic refuses fp32 contract
# precision on bf16 operands, and the chip tests ask for "highest"
# where they compare f32.
if not ON_CHIP:
    jax.config.update("jax_default_matmul_precision", "highest")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "chip: needs a real TPU backend (Mosaic-compiled kernels); "
        "skips elsewhere. Run with PADDLE_TPU_CHIP_TESTS=1 -m chip")
    # declare the marker tier-1 deselects with -m 'not slow' so the
    # @pytest.mark.slow tests don't warn PytestUnknownMarkWarning
    config.addinivalue_line(
        "markers",
        "slow: long-running test, deselected by tier-1 (-m 'not slow')")
    # chaos tests are the DETERMINISTIC fault-injection suite
    # (resilience/faults.py): seed-driven, no real signals/network, so
    # they run inside tier-1 ('not slow' keeps them selected) and can
    # also be run alone with -m chaos
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection test (tier-1; select "
        "alone with -m chaos)")
    # serving-engine suite (paddle_tpu/serving): in-process, CPU-fast,
    # runs inside tier-1; select alone with -m serving
    config.addinivalue_line(
        "markers",
        "serving: serving-engine test (tier-1; select alone with "
        "-m serving)")
    # pipelined-input suite (run_pipelined / DevicePrefetcher /
    # chunked train_from_dataset): CPU-fast, runs inside tier-1
    config.addinivalue_line(
        "markers",
        "pipeline: pipelined data-fed training test (tier-1; select "
        "alone with -m pipeline)")
    # health-plane suite (observability/health.py watchdog + flight
    # recorder + doctor): CPU-fast, runs inside tier-1
    config.addinivalue_line(
        "markers",
        "health: fleet health-plane test (tier-1; select alone with "
        "-m health)")
    # compile-plane suite (compile_cache, provenance ledger,
    # fusion_report): CPU-fast apart from two subprocess restarts
    config.addinivalue_line(
        "markers",
        "compile: compile-plane observability test (tier-1; select "
        "alone with -m compile)")
    # static-analysis suite (paddle_tpu/analysis verifier plane +
    # tools/lock_lint.py): pure-static, no tracing or XLA compiles
    config.addinivalue_line(
        "markers",
        "analysis: program-verifier / static-analysis test (tier-1; "
        "select alone with -m analysis)")
    # model-parallel suite (2D mesh training equality, sp attention
    # routing, sharded group inference): CPU-fast on the virtual
    # 8-device mesh, runs inside tier-1
    config.addinivalue_line(
        "markers",
        "mp: model-parallelism (dp × sp/tp/ep mesh) test (tier-1; "
        "select alone with -m mp)")
    # tiered-sparse suite (embedding cache / spill tier / q8 sparse
    # wire, docs/sparse.md): host-side numpy + loopback RPC, CPU-fast
    config.addinivalue_line(
        "markers",
        "sparse: tiered sparse embedding plane test (tier-1; select "
        "alone with -m sparse)")
    # closed-loop control-plane suite (observability/control.py:
    # policies, safety rails, ledger, autoscaling, doctor audit):
    # rail units are in-memory-fast; the subprocess/scenario cases
    # also carry -m chaos
    config.addinivalue_line(
        "markers",
        "control: closed-loop control-plane test (tier-1; select "
        "alone with -m control)")
    # step-engine suite (paddle_tpu/engine: the one composed step,
    # the runtime equality matrix, and static/runtime rule parity);
    # the full matrix sweep also carries -m slow
    config.addinivalue_line(
        "markers",
        "engine: composed step-engine test (tier-1; select alone "
        "with -m engine)")
    # pipeline-stage suite (engine/pipeline.py: gpipe/1F1B microbatch
    # schedules traced inside the one step); the sync-mode sweep
    # beyond one-cell-per-feature-pair also carries -m slow
    config.addinivalue_line(
        "markers",
        "pp: pipeline-stage (gpipe/1F1B in-step schedule) test "
        "(tier-1; select alone with -m pp)")
    # elastic-membership suite (trainer JOIN/LEAVE, pserver live
    # resharding, group-atomic scaling): loopback RPC, CPU-fast; the
    # acceptance scenario also carries -m chaos, the multi-seed sweep
    # and real-subprocess group scaling carry -m slow
    config.addinivalue_line(
        "markers",
        "elastic: elastic membership (join/leave/reshard) test "
        "(tier-1; select alone with -m elastic)")
    # sparse serving plane (serving/sparse.py: device tier + host
    # Tier 0 over the live pserver tables, bounded-staleness gate):
    # loopback RPC, CPU-fast; the train-and-serve acceptance scenario
    # also carries -m chaos, the multi-seed sweep -m slow
    config.addinivalue_line(
        "markers",
        "sparse_serving: sparse serving plane test (tier-1; select "
        "alone with -m sparse_serving)")
    # protocol-step fault-point plane (paddle_tpu/chaos): plane units
    # and one crash cell per protocol run inside tier-1; the full
    # (point x action) sweep grid also carries -m slow
    config.addinivalue_line(
        "markers",
        "faultpoint: protocol-step fault-injection test (tier-1 "
        "cells; full sweep grid is -m slow; select alone with "
        "-m faultpoint)")


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test gets fresh default programs, name generator, and global
    scope (the analog of OpTest's per-test scope)."""
    from paddle_tpu import framework
    from paddle_tpu.core import scope as scope_mod
    framework._reset_default_programs()
    scope_mod._reset_global_scope()
    # a leaked FaultPlan from one test must never fire inside the
    # next test's protocol traffic
    from paddle_tpu.chaos import faultpoints
    faultpoints.clear()
    yield
    faultpoints.clear()


@pytest.fixture
def rng():
    return np.random.RandomState(42)


@pytest.fixture
def fresh_traces():
    """The blocked flash wrappers are jitted on the shapes alone: a
    test that replaces their VMEM model's budget must not meet a trace
    made under another."""
    from paddle_tpu.ops.pallas import attention

    def clear():
        attention._flash_fwd.clear_cache()
        attention._flash_bwd.clear_cache()

    clear()
    yield
    clear()
