"""Test config: force CPU with 8 virtual devices BEFORE jax imports.

Mirrors the reference's fake-cluster strategy (SURVEY §4: multi-process on
localhost) — here SPMD needs no processes, just a virtual 8-device mesh via
xla_force_host_platform_device_count.
"""
import os

# force CPU unconditionally: unit tests must not burn (or depend on) the
# real TPU; the driver's bench run uses the chip.
os.environ["JAX_PLATFORMS"] = "cpu"
# deterministic fast lease-lapse in launcher/elastic tests (production
# default is 45s for saturated-host robustness; tests simulate death
# explicitly and need not wait that long)
os.environ.setdefault("PADDLE_HEARTBEAT_TTL", "20")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# hermetic tests: paddle_tpu points jax's persistent compilation cache
# at <repo>/.jax_cache by default, and a run must not depend on (or
# leave behind) executables another run compiled.  Tests that exercise
# the cache arm it explicitly through FLAGS_compile_cache_dir.
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle
    paddle.seed(1234)
    np.random.seed(1234)
    yield


@pytest.fixture(autouse=True)
def _verify_built_programs():
    """Every static Program built during a test must END the test
    verifier-clean (the PIR every-pass-leaves-verifiable-IR contract,
    enforced suite-wide).  Flag-gated: FLAGS_verify_built_programs=0
    disables; planted-defect tests opt out one program at a time via
    `prog._no_autoverify = True`."""
    if os.environ.get("FLAGS_verify_built_programs", "1") != "1":
        yield
        return
    import weakref
    import paddle_tpu.static as static
    created = []
    orig_init = static.Program.__init__

    def patched(self, *a, **k):
        orig_init(self, *a, **k)
        created.append(weakref.ref(self))

    static.Program.__init__ = patched
    try:
        yield
    finally:
        static.Program.__init__ = orig_init
    from paddle_tpu.analysis import verify_program
    for r in created:
        p = r()
        if p is None or getattr(p, "_no_autoverify", False):
            continue
        findings = verify_program(p, level="full")
        assert not findings, (
            "a static Program built during this test is not "
            "verifier-clean:\n" + "\n".join(
                f"  [{f.code}] {f.message}" for f in findings))


@pytest.fixture(autouse=True)
def _lint_fused_ce_logits():
    """Every ShardedTrainStep a test runs while FLAGS_fused_ce is on
    must END the test clean under lint_materialized_logits — the
    fused-loss contract (no [B, S, vocab] fp32 buffer anywhere in the
    jitted step), enforced suite-wide alongside the Program verifier.
    Zero cost for tests that never arm the flag.  Planted-defect tests
    opt out per step via `step._no_autolint = True`."""
    import weakref
    from paddle_tpu.framework.flags import get_flag
    from paddle_tpu.parallel.sharded_trainer import ShardedTrainStep
    recorded = []
    orig_prepare = ShardedTrainStep._prepare

    def patched(self, batch):
        if get_flag("fused_ce") and not any(
                r() is self for r, _ in recorded):
            recorded.append((weakref.ref(self), batch))
        return orig_prepare(self, batch)

    ShardedTrainStep._prepare = patched
    try:
        yield
    finally:
        ShardedTrainStep._prepare = orig_prepare
    if not recorded:
        return
    # linting RE-TRACES the step's python body, which reads the flag —
    # re-arm it so the trace takes the same fused path the test ran
    # (test-local flag fixtures tear down before this autouse one)
    from paddle_tpu.framework.flags import set_flags
    prev = get_flag("fused_ce")
    set_flags({"FLAGS_fused_ce": True})
    try:
        for ref, batch in recorded:
            step = ref()
            if step is None or getattr(step, "_no_autolint", False) \
                    or step._pipeline is not None:
                continue
            vocab = getattr(getattr(step.model, "config", None),
                            "vocab_size", None)
            if not vocab:
                continue
            # the fused forward gate is flag AND training — a test that
            # eval()s the model after its fused train steps must not
            # flip the retrace onto the unfused (lint-tripping) path
            was_training = step.model.training
            if not was_training:
                step.model.train()
            try:
                findings = step.lint(*batch, donation=False,
                                     transfers=False,
                                     logits=True).get("logits", [])
            finally:
                if not was_training:
                    step.model.eval()
            assert not findings, (
                "a fused-CE (FLAGS_fused_ce) train step built during "
                "this test materializes full fp32 logits:\n" + "\n".join(
                    f"  [{f.code}] {f.message}" for f in findings))
    finally:
        set_flags({"FLAGS_fused_ce": prev})


# ---------------------------------------------------------------------------
# fast tier (VERDICT r3 item 10): `-m fast` runs a <5-minute subset that
# still touches every subsystem; the full suite stays the completeness
# bar.  Modules are fast by default; the denylists below carve out the
# expensive compile/multiprocess/schedule-zoo tests.
# ---------------------------------------------------------------------------
_SLOW_MODULES = {
    # multi-process launch/elastic walls (heartbeat TTL waits)
    "test_elastic", "test_launch", "test_rpc", "test_elastic_resume",
    # trainer-compile zoo (checkpoint/guard planted-fault coverage)
    "test_fault_tolerance",
    # XLA CPU compile walls (model zoo, UNet, scanned pipelines)
    "test_vision_models", "test_unet", "test_gpt", "test_moe",
    "test_pipeline", "test_recompute", "test_long_context",
    "test_generation", "test_distributed", "test_op_registry",
    "test_distribution", "test_pallas_kernels",
    "test_eager_collectives",
}
# one representative per slow module keeps every subsystem in the tier
_FAST_PICKS = {
    "test_elastic": "test_elastic_exit_code_triggers_reform",
    "test_fault_tolerance": "test_sharded_trainer_resume_parity",
    "test_launch": "test_two_procs_env_wiring",
    "test_rpc": "test_rpc_two_workers",
    "test_vision_models": "test_forward_shape[squeezenet1_1]",
    "test_unet": "test_unet_forward_shape",
    "test_gpt": "test_gpt_trains",
    "test_moe": "test_naive_gate_dense_path_equals_dense",
    "test_pipeline": "test_pp_loss_matches_single_device[2-4-1F1B]",
    "test_recompute": "test_matches_plain_backward",
    "test_long_context":
        "test_sequence_parallel_linear_pair_matches_dense",
    "test_generation": "test_prefill_matches_full_forward",
    "test_distributed": "test_dp_matches_single",
    "test_op_registry": "test_registry_op_output[affine_channel]",
    "test_distribution": "test_sample_moments[normal]",
    "test_pallas_kernels": "test_forward[False]",
    "test_eager_collectives": "test_group_scoped_collectives_4proc",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "fast: <5-minute CPU subset covering every subsystem")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 run (-m 'not slow') "
        "— heavy multi-process end-to-end walls; covered in tier-1 by "
        "fast in-process twins")


def pytest_collection_modifyitems(config, items):
    seen_mods, matched = set(), set()
    for item in items:
        mod = item.module.__name__.rsplit(".", 1)[-1]
        seen_mods.add(mod)
        if mod not in _SLOW_MODULES:
            item.add_marker(pytest.mark.fast)
            continue
        pick = _FAST_PICKS.get(mod)
        if pick and item.name == pick:
            item.add_marker(pytest.mark.fast)
            matched.add(mod)
    # a renamed test must not silently drop its subsystem from the tier
    # — but only judge modules collected IN FULL (node-id / -k /
    # --deselect subsets legitimately omit the pick)
    sel = [a for a in config.invocation_params.args
           if isinstance(a, str)]
    partial = (bool(config.getoption("keyword", "") or "")
               or bool(config.getoption("deselect", None))
               or any("::" in a for a in sel))
    stale = [m for m in seen_mods & set(_SLOW_MODULES)
             if _FAST_PICKS.get(m) and m not in matched]
    if stale and not partial:
        raise pytest.UsageError(
            f"fast-tier picks no longer match a collected test: "
            f"{sorted(stale)} — update _FAST_PICKS in conftest.py")
