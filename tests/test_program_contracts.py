"""Program contracts: a feature that is off leaves nothing in a step
program, and a flag that only steers the host never reaches one.

Every feature of rounds 6-24 landed behind a flag with a "flags-off
program byte-identical" contract.  Those contracts are held here, on
the tiny models they were written against, on the CPU: the lowered
train step and the two lowered serve step programs (with their program
keys) are compared as text before, while and after a feature is armed.
A change to the step programs' signature, the chunk boundary or the
batcher's slot state is held to this file.

Contracts another tier-1 test already holds are not repeated:

  * flags-off executor replays run no verification and keep the
    replay-cache keys: tests/test_program_verifier.py
    `test_hot_path_runs_zero_verifications_with_flag_off`;
  * no guard ops in the flags-off train step, no checkpoint IO and no
    fault-registry hit on the flags-off step path:
    tests/test_fault_tolerance.py `test_flags_off_compiles_no_guard_ops`
    and `TestZeroOverhead`;
  * the trivial hybrid point is the plain trainer's program, and
    FLAGS_sep_ring_attention is inert off a sep mesh:
    tests/test_hybrid_engine.py
    `test_trivial_point_flags_off_hlo_identical`;
  * exactly two compiled serve programs under a mixed-SLO workload with
    the robustness flags on: tests/test_serve_robustness.py
    `test_flags_on_slo_mix_never_recompiles`;
  * a flip of FLAGS_weight_only_dtype fences cached programs and the
    restored flag hits them warm: tests/test_weight_only.py
    `test_program_cache_keys_guard_weight_only_flag`;
  * comm overlap engaging and staying bit-exact on a real mesh:
    tests/test_comm_overlap.py.
"""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.static as static
from paddle_tpu import telemetry
from paddle_tpu.distributed import checkpoint as ckpt
from paddle_tpu.distributed.topology import build_mesh
from paddle_tpu.framework import flags as flag_registry
from paddle_tpu.inference import ContinuousBatcher
from paddle_tpu.inference.generation import _program_cache_contains
from paddle_tpu.inference.router import ServeRouter
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.parallel import ShardedTrainStep


@pytest.fixture
def set_flags():
    """`paddle.set_flags`; every flag is back at the value the test
    found it with when the test ends, pass or fail."""
    found = {k: v["value"] for k, v in flag_registry.known_flags().items()}
    yield flag_registry.set_flags
    flag_registry.set_flags(found)


@pytest.fixture
def attach_flight_recorder(tmp_path):
    """Call it to attach a flight recorder of the test's own.  A
    recorder armed through FLAGS_flightrec_dir steps aside for the test
    and comes back; the telemetry plane is left pristine."""
    prior = telemetry.flightrec.detach()
    yield lambda: telemetry.flightrec.attach(str(tmp_path / "incidents"))
    telemetry.reset()
    telemetry.clear_report()
    telemetry.flightrec.restore(prior)


# ---------------------------------------------------------------------------
# the train step

def _one_chip_mesh():
    return build_mesh(devices=jax.devices()[:1])


class _MLP(paddle.nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = paddle.nn.Linear(8, 8)

    def forward(self, x):
        return self.fc(x)


def _mlp_step():
    """(step, x) of a one-layer MLP trainer; the flags in force when
    this is called are the ones its program is built under."""
    paddle.seed(0)
    m = _MLP()
    opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
    step = ShardedTrainStep(
        m, opt, _one_chip_mesh(),
        loss_fn=lambda o, y: paddle.nn.functional.mse_loss(o, y))
    return step, paddle.to_tensor(np.ones((4, 8), np.float32))


def _mlp_hlo():
    step, x = _mlp_step()
    return step.compiled_hlo(x, x, optimized=False)


def _llama_step():
    """(step, ids) of the tiny llama trainer, lowering only."""
    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny_config())
    opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters(),
                                 weight_decay=0.1)
    step = ShardedTrainStep(m, opt, _one_chip_mesh(), sharding_stage=0)
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 512, (2, 16)).astype(np.int32))
    return step, ids


def _llama_hlo():
    step, ids = _llama_step()
    return step.compiled_hlo(ids, ids, optimized=False)


def _saved_bytes(d):
    """(manifest, shard) bytes of one `save_state_dict` into `d`."""
    ckpt.save_state_dict(
        {"w": paddle.to_tensor(np.ones((8, 8), np.float32))}, str(d))
    return ((d / "metadata.json").read_bytes(),
            (d / "0.distcp").read_bytes())


def test_sharded_save_flag_round_trip_keeps_the_flags_off_format(
        tmp_path, set_flags):
    """Arming and disarming FLAGS_ckpt_save_sharded leaves the flags-off
    manifest and shard container byte-identical (and the armed save
    works)."""
    for name in ("before", "armed", "after"):
        (tmp_path / name).mkdir()
    manifest, shard = _saved_bytes(tmp_path / "before")
    set_flags({"FLAGS_ckpt_save_sharded": True})
    assert _saved_bytes(tmp_path / "armed")[0]
    set_flags({"FLAGS_ckpt_save_sharded": False})
    assert _saved_bytes(tmp_path / "after") == (manifest, shard)


def test_sharded_save_flag_never_reaches_the_train_step(set_flags):
    """FLAGS_ckpt_save_sharded is host-plane: the train step reads the
    same armed and after the round trip."""
    step, x = _mlp_step()
    off = step.compiled_hlo(x, x, optimized=False)
    set_flags({"FLAGS_ckpt_save_sharded": True})
    assert step.compiled_hlo(x, x, optimized=False) == off
    set_flags({"FLAGS_ckpt_save_sharded": False})
    assert step.compiled_hlo(x, x, optimized=False) == off


@pytest.fixture
def fusion_builds(set_flags):
    """The tiny llama step built flags-off, with FLAGS_fused_ce and
    FLAGS_bf16_adamw_moments on, and flags-off again: (hlo, optimizer
    state keys) each."""
    def build(on):
        set_flags({"FLAGS_fused_ce": on, "FLAGS_bf16_adamw_moments": on})
        step, ids = _llama_step()
        return (step.compiled_hlo(ids, ids, optimized=False),
                set(step._opt_states[0]))

    return build(False), build(True), build(False)


def test_fusion_flags_leave_no_residue(fusion_builds):
    (off, _), _, (off_again, _) = fusion_builds
    assert off == off_again


def test_fusion_flags_reach_the_program_and_the_optimizer_state(
        fusion_builds):
    """The toggle above proves something only if the flags engage: the
    program differs, and the error-feedback moment exists only under
    FLAGS_bf16_adamw_moments."""
    (off, keys_off), (on, keys_on), _ = fusion_builds
    assert on != off
    assert "ef" not in keys_off and "ef" in keys_on


@pytest.fixture
def overlap_builds(set_flags):
    """The tiny llama step on a one-chip mesh built with
    FLAGS_comm_overlap off, on and off again: (hlo, overlap plan)."""
    def build(on):
        set_flags({"FLAGS_comm_overlap": on})
        step, ids = _llama_step()
        return (step.compiled_hlo(ids, ids, optimized=False),
                step._overlap_plan)

    return build(False), build(True), build(False)


def test_comm_overlap_flag_leaves_no_residue(overlap_builds):
    (off, _), _, (off_again, _) = overlap_builds
    assert off == off_again


def test_comm_overlap_is_inert_on_one_chip(overlap_builds):
    """No cross-rank traffic exists to overlap on a one-chip mesh: the
    plan declines to build and the program is the flags-off one."""
    (off, _), (on, plan), _ = overlap_builds
    assert plan is None
    assert on == off


def test_observability_surface_leaves_the_train_step_identical(
        tmp_path, set_flags, attach_flight_recorder):
    """A sink, the flight recorder, a fleet identity, the AOT store and
    the straggler and drift floors armed at once (FLAGS_numerics_stats
    left unset) are host-side: the train step they watch is the
    flags-off program, before, while and after."""
    assert not telemetry.active()
    assert not flag_registry.get_flag("compile_cache_dir")
    off = _mlp_hlo()
    sink = telemetry.attach_jsonl(str(tmp_path / "s.jsonl"))
    attach_flight_recorder()
    telemetry.set_rank(0, 2)
    set_flags({"FLAGS_compile_cache_dir": "1",
               "FLAGS_straggler_skew_ms": 50.0,
               "FLAGS_mfu_floor": 0.5})
    step, x = _mlp_step()
    armed = step.compiled_hlo(x, x, optimized=False)
    step(x, x)                  # the armed path runs, not only lowers
    set_flags({"FLAGS_compile_cache_dir": "",
               "FLAGS_straggler_skew_ms": 0.0,
               "FLAGS_mfu_floor": 0.0})
    telemetry.remove_sink(sink)
    telemetry.flightrec.detach()
    assert off == armed == _mlp_hlo()


def test_numerics_stats_reaches_the_train_step(set_flags):
    """The numerics plane is a PROGRAM switch: were it vacuous, the
    identity above would say nothing about it."""
    off = _mlp_hlo()
    set_flags({"FLAGS_numerics_stats": True})
    assert _mlp_hlo() != off


def test_executor_replays_publish_nothing():
    """Flags-off static-executor replays with a sink attached neither
    grow the replay-cache key set nor publish an event."""
    static.enable_static()
    probe = telemetry.MemorySink()
    try:
        main = static.Program()
        with static.program_guard(main, static.Program()):
            x = static.data("x", [2, 4], "float32")
            w = paddle.to_tensor(np.ones((4, 3), np.float32))
            loss = paddle.matmul(x, w).mean()
        exe = static.Executor()
        feed = {"x": np.ones((2, 4), np.float32)}
        exe.run(main, feed=feed, fetch_list=[loss])
        keys = set(main._exec_cache)
        telemetry.add_sink(probe)
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss])
    finally:
        telemetry.remove_sink(probe)
        static.disable_static()
    assert set(main._exec_cache) == keys
    assert not probe.records


# ---------------------------------------------------------------------------
# the serve step programs

_GEOMETRY = dict(max_batch_size=2, max_len=32, chunk=4, prefill_chunk=4)


def _serve_model(seed):
    paddle.seed(seed)
    return LlamaForCausalLM(llama_tiny_config(
        num_hidden_layers=1, hidden_size=32, intermediate_size=64,
        num_attention_heads=2, num_key_value_heads=2, vocab_size=64))


def _fingerprint(model, **kw):
    """(the decode and admit program keys, both lowered step programs as
    text) of a batcher built under the flags in force."""
    bat = ContinuousBatcher(model, **_GEOMETRY, **kw)
    keys = (bat._program_key(1, bat.chunk),
            bat._program_key(bat.prefill_chunk, bat.admit_steps))
    hlo = (bat.lower_step(mixed=False).as_text(),
           bat.lower_step(mixed=True).as_text())
    return keys, hlo


@pytest.fixture(scope="module")
def serve_model():
    return _serve_model(3)


@pytest.fixture(scope="module")
def serve_off(serve_model):
    """The flags-off program keys and step programs."""
    return _fingerprint(serve_model)


def _assert_serve_programs_unmoved(model, off, **kw):
    """Built under the flags in force now, the serve programs and their
    keys are `off`'s (the keys first: their difference reads in a
    line)."""
    keys, hlo = _fingerprint(model, **kw)
    assert keys == off[0]
    assert hlo == off[1]


def test_slo_flags_and_flight_recorder_leave_the_serve_programs_identical(
        serve_model, serve_off, set_flags, attach_flight_recorder):
    """SLO admission, deadlines and shedding are host-plane control
    flow, the recorder a plain sink: keys and lowered programs are the
    flags-off ones with all of it armed, and after."""
    attach_flight_recorder()
    set_flags({"FLAGS_serve_queue_depth": 8,
               "FLAGS_serve_default_deadline_ms": 60000.0})
    _assert_serve_programs_unmoved(serve_model, serve_off)
    set_flags({"FLAGS_serve_queue_depth": 0,
               "FLAGS_serve_default_deadline_ms": 0.0})
    telemetry.flightrec.detach()
    _assert_serve_programs_unmoved(serve_model, serve_off)


class _CountingKV:
    """Every KV verb the daemon could issue, counted."""

    def __init__(self, inner):
        self._inner = inner
        self.calls = 0

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if not callable(attr):
            return attr

        def wrapped(*a, **k):
            self.calls += 1
            return attr(*a, **k)
        return wrapped


def test_autoscaler_off_is_one_flag_read(serve_model):
    """With FLAGS_autoscale unset a daemon's tick() is the flag check:
    no lease, no journal, no recovery scan reaches the KV plane."""
    from paddle_tpu.fleet import AutoscalerDaemon
    from paddle_tpu.fleet.autoscaler import _LocalKV
    kv = _CountingKV(_LocalKV())
    router = ServeRouter(
        batchers=[ContinuousBatcher(serve_model, **_GEOMETRY)])
    daemon = AutoscalerDaemon(router, kv=kv)
    for _ in range(4):
        assert daemon.tick().get("status") == "disabled"
    assert kv.calls == 0


def test_autoscale_flag_leaves_the_serve_programs_identical(
        serve_model, serve_off, set_flags):
    import paddle_tpu.fleet  # noqa: F401  (the package loaded, as armed)
    set_flags({"FLAGS_autoscale": True})
    _assert_serve_programs_unmoved(serve_model, serve_off)
    set_flags({"FLAGS_autoscale": False})
    _assert_serve_programs_unmoved(serve_model, serve_off)


def test_unified_fleet_compiles_no_page_programs():
    """Disaggregation unused: a unified fleet run (no role ever set)
    hands nothing off and compiles neither page program."""
    model = _serve_model(3)
    bat = ContinuousBatcher(model, **_GEOMETRY)
    rng = np.random.RandomState(1)
    router = ServeRouter(batchers=[ContinuousBatcher(model, **_GEOMETRY)
                                   for _ in range(2)])
    for n in (5, 7, 6):
        router.submit(rng.randint(1, 64, n).astype(np.int32), 4)
    assert len(router.run()) == 3
    assert router.stats()["handoffs"] == 0
    for name in ("serve_page_export", "serve_page_import"):
        key = (name, bat.num_pages, bat.page_size, bat.pages_per_slot,
               bat._kv_dtype)
        assert not _program_cache_contains(model, key), key


def test_disagg_flags_leave_the_serve_programs_identical(
        serve_model, serve_off, set_flags):
    set_flags({"FLAGS_serve_disagg": True,
               "FLAGS_router_migration_budget": 4})
    _assert_serve_programs_unmoved(serve_model, serve_off)
    set_flags({"FLAGS_serve_disagg": False,
               "FLAGS_router_migration_budget": 0})
    _assert_serve_programs_unmoved(serve_model, serve_off)


# FLAGS_weight_only_dtype and speculation against an UNQUANTIZED,
# non-speculative batcher: four contracts on one model.

_PLAIN = dict(weight_only_dtype="none")

@pytest.fixture(scope="module")
def plain_model():
    return _serve_model(7)


@pytest.fixture(scope="module")
def plain_off(plain_model):
    return _fingerprint(plain_model, **_PLAIN)


def test_weight_only_flag_leaves_unquantized_serve_programs_identical(
        plain_model, plain_off, set_flags):
    """(a) The flag changes the program-cache fingerprint (its fence,
    tests/test_weight_only.py), never the keys or the lowered programs
    of a batcher that opted out of quantization."""
    set_flags({"FLAGS_weight_only_dtype": "int8"})
    _assert_serve_programs_unmoved(plain_model, plain_off, **_PLAIN)
    set_flags({"FLAGS_weight_only_dtype": "none"})
    _assert_serve_programs_unmoved(plain_model, plain_off, **_PLAIN)


def test_weight_only_flag_never_reaches_the_train_step(set_flags):
    """(b) The llama train step does not read the serving flags."""
    off = _llama_hlo()
    set_flags({"FLAGS_weight_only_dtype": "int8"})
    assert _llama_hlo() == off


def test_speculation_swaps_the_decode_program_and_gives_it_back(
        plain_model, plain_off):
    """(c) Speculation is a different decode program under a different
    key, so neither can stand in for the other; (d) the default
    constructor gives the original back byte for byte."""
    keys_off, hlo_off = plain_off
    keys, hlo = _fingerprint(plain_model, spec_tokens=2, draft_layers=1,
                             **_PLAIN)
    assert keys[0] != keys_off[0]
    assert hlo[0] != hlo_off[0]
    _assert_serve_programs_unmoved(plain_model, plain_off, **_PLAIN)


def test_serve_programs_donate_every_carry(plain_model):
    """The draft-and-verify decode scan, the draft-carrying admit scan
    and the plain pair alias every carry: a forgotten donation doubles
    the KV pool in device memory."""
    from paddle_tpu.analysis import lint_serve_programs
    for kw in (dict(spec_tokens=2, draft_layers=1), {}):
        bat = ContinuousBatcher(plain_model, **_GEOMETRY, **_PLAIN, **kw)
        assert not lint_serve_programs(bat), kw


def test_block_schedule_leaves_a_token_models_programs_alone(plain_model):
    """A model with block length 1 compiles step programs without a
    block carry, a `diffusion.` scope or an extra output; one that
    generates by diffusion has all three, under keys of their own, and
    donates the block with the other carries."""
    from paddle_tpu.analysis import lint_serve_programs
    bat = ContinuousBatcher(plain_model, **_GEOMETRY, **_PLAIN)
    assert bat.block_len == 1 and bat._tok.shape == (2,)
    for mixed in (False, True):
        lowered = bat.lower_step(mixed=mixed)
        assert "diffusion." not in lowered.as_text(debug_info=True)
        # the eight carries, the tokens, the two token counts
        assert len(lowered.out_info) == 11
    paddle.seed(7)
    blocks = LlamaForCausalLM(llama_tiny_config(
        num_hidden_layers=1, hidden_size=32, intermediate_size=64,
        num_attention_heads=2, num_key_value_heads=2, vocab_size=64,
        block_length=4, denoising_steps=2, mask_token_id=63))
    dif = ContinuousBatcher(blocks, **_GEOMETRY, **_PLAIN)
    assert sorted(dif._tok) == ["fixed_at", "ids", "masked", "step"]
    assert dif._program_key(4, dif.chunk) != bat._program_key(1, bat.chunk)
    for mixed in (False, True):
        lowered = dif.lower_step(mixed=mixed)
        text = lowered.as_text(debug_info=True)
        assert "diffusion.sample" in text and "diffusion.update" in text
        # the block where the token was, and two more outputs: the
        # schedule's counts and the tokens' passes
        assert sorted(lowered.out_info[2]) == sorted(dif._tok)
        assert len(lowered.out_info) == 13
    assert not lint_serve_programs(dif)


def _kind_models():
    """(name, model, batcher knobs) of the four kinds of model the accepted
    cells serve, tiny: dense GQA, MHA with an int8 pool, latent rows, the
    block schedule."""
    small = dict(num_hidden_layers=2, hidden_size=32, intermediate_size=64,
                 num_attention_heads=4, vocab_size=64)

    def build(**cfg):
        paddle.seed(11)
        return LlamaForCausalLM(llama_tiny_config(**dict(small, **cfg)))
    return [
        ("dense_gqa", lambda **kw: build(num_key_value_heads=2, **kw), {}),
        ("mha_int8", lambda **kw: build(num_key_value_heads=4, **kw),
         dict(kv_dtype="int8")),
        ("latent", lambda **kw: build(
            num_key_value_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=8, v_head_dim=8, **kw), {}),
        ("block", lambda **kw: build(
            num_key_value_heads=2, block_length=4, denoising_steps=2,
            mask_token_id=63, **kw), {})]


@pytest.mark.parametrize("which", range(4),
                         ids=["dense_gqa", "mha_int8", "latent", "block"])
def test_kinds_of_layer_leave_a_model_without_them_alone(which):
    """A model without sliding-window layers compiles the step programs it
    always did: no pool of a second kind, no `attn.window` / `attn.full`
    scope, no ring in the program key, no count by kind in stats(); and a
    model whose `layer_types` call EVERY layer `full_attention` lowers to
    the same text as one that names no kinds (PR 34 held the four models'
    eight programs, and the paged kernel's jaxpr in six variants, to the
    parent commit's text: PERF.md section 6)."""
    _, build, knobs = _kind_models()[which]
    plain = build()
    bat = ContinuousBatcher(plain, **_GEOMETRY, **_PLAIN, **knobs)
    assert bat._kinds is None and bat.ring_pages == 0
    assert "ring" not in bat._program_key(1, bat.chunk)
    assert not set(bat._cache) & {"k_window", "v_window"}
    assert not [k for k in bat.stats() if k.startswith(
        ("kv_pages_walked_", "kv_pages_window_", "kv_pool_bytes"))]
    texts = []
    for mixed in (False, True):
        lowered = bat.lower_step(mixed=mixed)
        assert "attn.window" not in lowered.as_text(debug_info=True)
        assert "attn.full" not in lowered.as_text(debug_info=True)
        texts.append(lowered.as_text())
    named = ContinuousBatcher(
        build(layer_types=("full_attention",) * 2, sliding_window=8),
        **_GEOMETRY, **_PLAIN, **knobs)
    assert named._kinds is None
    assert [named.lower_step(mixed=m).as_text()
            for m in (False, True)] == texts


def _conditionals(lowered):
    """The conditionals of a lowered program (`jax.lax.switch` and
    `jax.lax.cond` both lower to `stablehlo.case`, or `stablehlo.if`)."""
    text = lowered.as_text()
    return text.count("stablehlo.case") + text.count("stablehlo.if")


@pytest.mark.parametrize("which", range(4),
                         ids=["dense_gqa", "mha_int8", "latent", "block"])
def test_a_model_without_expert_layers_compiles_no_conditional(which):
    """The expert layer chooses the length of its sorted buffer on the
    device (`jax.lax.switch`, PR 35); a model without expert layers has
    nothing to choose: neither serve step program of the four kinds of
    model holds a conditional (the dense and train cells' programs were held
    to the parent commit's text once: CHANGES.md, PR 35)."""
    _, build, knobs = _kind_models()[which]
    bat = ContinuousBatcher(build(), **_GEOMETRY, **_PLAIN, **knobs)
    assert bat.model.step_counter_names() == ()
    for mixed in (False, True):
        assert _conditionals(bat.lower_step(mixed=mixed)) == 0


def test_the_train_step_and_the_expert_ladder_by_conditionals(monkeypatch):
    """The tiny llama train step holds no conditional either; the serve
    programs of a model WITH expert layers hold one a layer where the
    ladder has more than one rung (here a rung may be one row), so the
    count above is a count of something."""
    step, ids = _llama_step()
    assert "stablehlo.case" not in step.compiled_hlo(ids, ids,
                                                     optimized=False)
    from paddle_tpu.incubate.distributed.models import moe
    monkeypatch.setattr(moe, "MIN_RUNG_ROWS", 1)
    paddle.seed(11)
    experts = LlamaForCausalLM(llama_tiny_config(
        num_hidden_layers=2, hidden_size=32, intermediate_size=64,
        num_attention_heads=4, num_key_value_heads=2, vocab_size=64,
        moe_num_experts=4, moe_top_k=2, moe_gate="naive"))
    bat = ContinuousBatcher(experts, **_GEOMETRY, **_PLAIN)
    assert bat.model.step_counter_names()
    for mixed in (False, True):
        assert _conditionals(bat.lower_step(mixed=mixed)) == 2
