"""The program's own spans on the profiler's clock (ISSUE 25).

While a profiler session runs, `telemetry.span` lies in the session's
`.xplane.pb` beside the device's operations.  Under test, from such a
trace taken on the CPU:

  * `ContinuousBatcher.step()` is a `serve.step` holding `serve.evict`,
    `serve.admit`, `serve.dispatch`, `serve.device_wait`,
    `serve.harvest` (with `serve.deliver` inside) in that order, the
    `serve.req.*` markers name the ids `submit()` returned, and the six
    phase durations the batcher keeps add up to no more than the step;
  * `ShardedTrainStep.__call__` / `run_steps` is a `train.step` holding
    `train.prepare`, `train.dispatch`, `train.writeback`;
  * the step program's scopes (`train.optimizer`, `train.guard`) are on
    the update's operations in the compiled HLO, and change nothing of
    the program but that metadata.
"""
import contextlib
import glob
import re

import numpy as np
import pytest

import jax
import paddle_tpu as paddle
from paddle_tpu import telemetry
from paddle_tpu.inference.serving import PHASES


@pytest.fixture(autouse=True)
def _clean_plane():
    for s in telemetry.sinks():
        telemetry.remove_sink(s)
    yield
    for s in telemetry.sinks():
        telemetry.remove_sink(s)


@contextlib.contextmanager
def _traced(tmp_path):
    """Run the body under a profiler session; yields a list that holds,
    afterwards, the program's spans of the busiest host thread as
    (name, start_ns, end_ns, ids) by start."""
    spans = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield spans
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    (host,) = [p for p in data.planes if p.name == "/host:CPU"]
    for line in host.lines:
        found = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                  dict(e.stats)) for e in line.events
                 if e.name.startswith(("serve.", "train."))]
        if len(found) > len(spans):
            spans[:] = sorted(found, key=lambda s: s[1])


def _inside(spans, outer):
    """The spans that lie in `outer`, by start."""
    return [s for s in spans if s is not outer
            and outer[1] <= s[1] and s[2] <= outer[2]]


def _tiny_batcher(**kw):
    from paddle_tpu.inference import ContinuousBatcher
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    paddle.seed(13)
    model = LlamaForCausalLM(llama_tiny_config(
        num_hidden_layers=1, hidden_size=32, intermediate_size=64,
        num_attention_heads=2, num_key_value_heads=2, vocab_size=64))
    model.eval()
    return ContinuousBatcher(model, max_batch_size=2, max_len=32, chunk=4,
                             prefill_chunk=4, **kw)


def _prompt(rng, n):
    return rng.randint(1, 64, n).astype(np.int32)


def test_serve_step_split_in_the_profilers_trace(tmp_path):
    rng = np.random.RandomState(2)
    bat = _tiny_batcher()
    bat.submit(_prompt(rng, 4), 4)
    bat.run()                       # both step programs compiled
    sink = telemetry.add_sink(telemetry.MemorySink())
    delivered = []
    with _traced(tmp_path) as spans:
        ids = [bat.submit(_prompt(rng, 5), 6,
                          on_token=lambda r, t, d: delivered.append(len(t)))
               for _ in range(3)]       # two slots: the third one queues
        done = bat.run()
    telemetry.remove_sink(sink)
    assert sorted(done)[-3:] == ids

    steps = [s for s in spans if s[0] == "serve.step"]
    assert steps and [s[3]["chunk"] for s in steps] == sorted(
        s[3]["chunk"] for s in steps)
    ran = 0
    for step in steps:
        names = [s[0] for s in _inside(spans, step)
                 if not s[0].startswith("serve.req.")]
        if "serve.dispatch" not in names:
            # a step that found nothing live runs no chunk
            assert names in (["serve.evict", "serve.admit"],
                             ["serve.evict"])
            continue
        ran += 1
        assert names == ["serve.evict", "serve.admit", "serve.dispatch",
                         "serve.device_wait", "serve.harvest",
                         "serve.deliver", "serve.evict"]
        inner = {s[0]: s for s in _inside(spans, step)}
        # deliver lies in harvest, and the phases carry the step's chunk
        assert inner["serve.deliver"] in _inside(spans,
                                                 inner["serve.harvest"])
        for name in ("serve.dispatch", "serve.device_wait",
                     "serve.harvest"):
            assert inner[name][3]["chunk"] == step[3]["chunk"]
        assert inner["serve.dispatch"][3]["kind"] in ("admit", "decode")
        assert inner["serve.dispatch"][3]["kind"] == \
            inner["serve.device_wait"][3]["kind"]
    assert ran >= 4
    assert sum(s[3]["admitted"] for s in spans
               if s[0] == "serve.admit") == 3
    assert sum(s[3]["tokens"] for s in spans
               if s[0] == "serve.deliver") == sum(delivered) == 18

    # the markers name the requests submit() returned, each in a step
    # whose chunk number the request keeps
    for marker, attr in (("serve.req.admit", "admit_chunk"),
                         ("serve.req.first_token", "first_token_chunk"),
                         ("serve.req.done", "done_chunk")):
        marks = [s for s in spans if s[0] == marker]
        assert sorted(m[3]["req"] for m in marks) == ids
        for m in marks:
            (step,) = [s for s in steps if m in _inside(spans, s)]
            assert m[3]["chunk"] == step[3]["chunk"]
            assert getattr(bat._finished[m[3]["req"]], attr) == \
                m[3]["chunk"]
    reqs = {r["req"]: r for r in sink.records
            if r["event"] == "serve.request"}
    for rid in ids:
        r = reqs[rid]
        assert 0 < r["admit_chunk"] <= r["first_token_chunk"] \
            <= r["done_chunk"]
    # the queued request was admitted when a slot came free
    assert reqs[ids[2]]["admit_chunk"] > reqs[ids[0]]["admit_chunk"]

    # each chunk's six phase durations against its step's, both by the
    # host's clock (the sink's records)
    step_ms = {r["chunk"]: r["dur_ms"] for r in sink.records
               if r["event"] == "serve.step"}
    chunks = [r for r in sink.records if r["event"] == "serve.chunk"]
    assert len(chunks) == ran
    for c in chunks:
        phases = [c[f"{k}_ms"] for k in PHASES]
        assert all(v >= 0 for v in phases)
        assert sum(phases) <= step_ms[c["chunk"]] + 1e-2
        assert c["device_wait_ms"] > 0 and c["dispatch_ms"] > 0


def test_phase_window_answers_without_a_listener():
    rng = np.random.RandomState(3)
    bat = _tiny_batcher()
    assert bat.stats()["phase_ms"] == {
        k: {"p50": 0.0, "max": 0.0} for k in PHASES}
    for _ in range(2):              # the second pass runs steady chunks
        bat.submit(_prompt(rng, 5), 6)
        bat.run()
    assert not telemetry.active()
    got = bat.stats()["phase_ms"]
    assert list(got) == list(PHASES)
    assert got["device_wait"]["p50"] > 0 and got["dispatch"]["p50"] > 0
    for k in PHASES:
        assert 0 <= got[k]["p50"] <= got[k]["max"]
    # bounded like the chunk times, and of steady chunks only
    assert len(bat._phase_times) == len(bat._chunk_times) <= 1024


def test_chunk_fault_closes_the_dispatch_span_with_its_error():
    from paddle_tpu.distributed import fault
    rng = np.random.RandomState(4)
    bat = _tiny_batcher()
    bat.submit(_prompt(rng, 4), 4)
    sink = telemetry.add_sink(telemetry.MemorySink())
    with fault.scope("serve.chunk:step=1:mode=error"):
        bat.run()
    telemetry.remove_sink(sink)
    bad = [r for r in sink.records if r["event"] == "serve.dispatch"
           and "error" in r]
    assert len(bad) == 1 and bad[0]["error"] == "FaultError"
    assert bad[0]["parent"] == "serve.step"
    # the chunk that never ran left no phase record and no chunk event
    faulted = bad[0]["chunk"]
    chunks = [r["chunk"] for r in sink.records
              if r["event"] == "serve.chunk"]
    assert chunks[0] == faulted and len(chunks) == len(set(chunks))


def _tiny_trainer():
    from paddle_tpu.parallel import ShardedTrainStep
    from paddle_tpu.distributed.topology import build_mesh
    paddle.seed(0)
    m = paddle.nn.Sequential(paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
                             paddle.nn.Linear(16, 8))
    opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
    step = ShardedTrainStep(
        m, opt, build_mesh(devices=jax.devices()[:1]),
        loss_fn=lambda o, y: paddle.nn.functional.mse_loss(o, y))
    return step, paddle.to_tensor(np.ones((4, 8), np.float32))


def test_train_step_split_in_the_profilers_trace(tmp_path):
    step, x = _tiny_trainer()
    step(x, x)
    sx = paddle.to_tensor(np.ones((3, 4, 8), np.float32))
    step.run_steps(sx, sx)          # both programs compiled: steps 1-4
    with _traced(tmp_path) as spans:
        step(x, x)
        step(x, x)
        step.run_steps(sx, sx)
    calls = [s for s in spans if s[0] == "train.step"]
    assert [(c[3]["step"], c[3]["k"]) for c in calls] == [
        (5, 1), (6, 1), (9, 3)]
    for call in calls:
        inner = _inside(spans, call)
        assert [s[0] for s in inner] == [
            "train.prepare", "train.dispatch", "train.writeback"]
        for a, b in zip(inner, inner[1:]):
            assert a[2] <= b[1]             # one after the other
        assert sum(s[2] - s[1] for s in inner) <= call[2] - call[1]
    assert len(spans) == 4 * len(calls)


def _strip_metadata(hlo_text):
    """The HLO text without what names and places its operations: each
    instruction's `metadata={...}` and the module's tables of files,
    functions and stack frames that those point into."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo_text)
    return "\n".join(
        ln for ln in text.splitlines()
        if not re.match(r"(FileNames|FunctionNames|FileLocations|"
                        r"StackFrames)$|\d+ ", ln))


def test_step_program_scopes_are_metadata_alone(monkeypatch):
    paddle.set_flags({"FLAGS_skip_nonfinite_steps": True})
    try:
        step, x = _tiny_trainer()
        scoped = step.compiled_hlo(x, x)
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        step, x = _tiny_trainer()
        bare = step.compiled_hlo(x, x)
    finally:
        paddle.set_flags({"FLAGS_skip_nonfinite_steps": False})
    assert "train.optimizer" not in bare and "train.guard" not in bare
    # the update's operations carry the optimizer's scope: Adam's square
    # root is nowhere else in this step
    roots = [ln for ln in scoped.splitlines()
             if re.search(r"= \S+ (sqrt|rsqrt)\(", ln)]
    assert roots and all("train.optimizer" in ln for ln in roots)
    assert any("train.guard" in ln and " select(" in ln
               for ln in scoped.splitlines())
    # forward and backward keep the names jax gives them
    assert "transpose(jvp(" in scoped and "train." not in "".join(
        ln for ln in scoped.splitlines() if "transpose(jvp(" in ln)
    assert _strip_metadata(scoped) != scoped
    assert _strip_metadata(scoped) == _strip_metadata(bare)


# -- the serve step program's scopes (ISSUE 36) ------------------------------

_SERVE_SMALL = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
                    num_hidden_layers=4, num_attention_heads=4,
                    max_position_embeddings=64)
_EXPERTS = dict(moe_num_experts=4, moe_top_k=2, moe_intermediate_size=16)
# the four serve cells' model classes, tiny
SERVE_MODELS = {
    "dense_mha": dict(num_key_value_heads=4),
    "kinds_gqa": dict(
        num_key_value_heads=2, head_dim=8, use_qk_norm=True,
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
        sliding_window=8, rope_layer_types=("sliding_attention",)),
    "latent_sigmoid": dict(
        num_key_value_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, first_k_dense_replace=1,
        moe_gate="sigmoid", moe_first_expert=4, moe_router_width=8,
        moe_shared_experts=1, moe_routed_scaling=2.5, moe_router_bias=True,
        **dict(_EXPERTS, moe_num_experts=4)),
    "block_softmax": dict(
        num_key_value_heads=2, block_length=4, denoising_steps=2,
        mask_token_id=63, moe_gate="naive", moe_expert_bias=False,
        **_EXPERTS),
}
_SERVE_GEOMETRY = dict(max_batch_size=2, max_len=32, chunk=4,
                       prefill_chunk=4)


def _serve_programs(which):
    """Both step programs (decode, admission) of a fresh tiny batcher,
    compiled, as text."""
    from paddle_tpu.inference import ContinuousBatcher
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(11)
    model = LlamaForCausalLM(LlamaConfig(**dict(_SERVE_SMALL,
                                                **SERVE_MODELS[which])))
    model.eval()
    bat = ContinuousBatcher(model, **_SERVE_GEOMETRY)
    return [bat.lower_step(mixed=mixed).compile().as_text()
            for mixed in (False, True)]


_SCOPED = {}


def _scoped_programs(which):
    if which not in _SCOPED:
        _SCOPED[which] = _serve_programs(which)
    return _SCOPED[which]


def _serve_parts():
    import os
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from metrics import _serve_parts
    return _serve_parts


def _renamed(hlo_text):
    """The text with each `%name` replaced by its order of first
    appearance: where the compiler numbers two equal instructions in
    another order, the program is the same."""
    names = {}
    return re.sub(r"%[\w.\-]+", lambda m: names.setdefault(
        m.group(0), f"%{len(names)}"), hlo_text)


@pytest.mark.parametrize("which", sorted(SERVE_MODELS))
def test_serve_step_scopes_are_metadata_alone(which, monkeypatch):
    """The scopes that split the serve step by layer (`attn.project`,
    `attn.cache_write`, `attn.attend`, `mlp`, `llama.embed`,
    `llama.lm_head`, `serve.sample`) name operations and change nothing
    else: both compiled step programs of each serve model class equal, once
    metadata is stripped, those compiled with `jax.named_scope` nulled."""
    scoped = _scoped_programs(which)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _serve_programs(which)
    latent = "latent" in which
    for text, plain in zip(scoped, bare):
        for scope in ("llama.embed", "llama.lm_head", "llama.norm",
                      "mla.project" if latent else "attn.project",
                      "mla.attend" if latent else "attn.attend"):
            assert scope in text and scope not in plain, scope
        assert ("serve.sample" in text) == ("block" not in which)
        assert ("attn.window/attn.attend" in text) == ("kinds" in which)
        assert _strip_metadata(text) != text
        assert _renamed(_strip_metadata(text)) == \
            _renamed(_strip_metadata(plain))


@pytest.mark.parametrize("which", sorted(SERVE_MODELS))
def test_every_product_of_the_serve_step_lies_in_a_named_part(which):
    """Every `dot`, `convolution` and `custom-call` of both compiled step
    programs goes to a named part of `serve_step_device_ms` (the benchmark's
    table, first match wins), none to `other`; and each part the model
    has holds some."""
    parts = _serve_parts()
    want = {"projections", "attention", "head"}
    cfg = SERVE_MODELS[which]
    if "moe_gate" in cfg:
        want.add("experts")
    if cfg.get("moe_gate") != "naive":
        want.add("mlp")            # dense layers, or a first dense layer
    for text in _scoped_programs(which):
        found = set()
        for line in text.splitlines():
            if not re.search(r"= \S+ (dot|convolution|custom-call)\(",
                             line):
                continue
            op = re.search(r'op_name="([^"]*)"', line)
            part = parts.part_of(op.group(1) if op else "")
            assert part != "other", line.strip()[:200]
            found.add(part)
        assert found == want


def test_the_first_matching_part_takes_an_operation():
    parts = _serve_parts()
    layer = "jit(serve_step)/while/body/llama.layer4/"
    assert parts.part_of(layer + "attn.window/attn.attend/pallas_call") \
        == "attention"
    assert parts.part_of(layer + "moe.experts/mlp/dot_general") == "experts"
    assert parts.part_of(layer + "attn.project/dot_general") \
        == "projections"
    assert parts.part_of("ragged-dot-none") == "experts"
    assert parts.part_of(layer + "rms_norm_fwd/pallas_call") == "other"


def test_lanes_valid_is_the_hand_count():
    """One admission chunk of 4 steps x 2 slots x 4 lanes over prompts of
    6 and 3 tokens: slot 0 carries 4 + 2 prompt tokens and then 1 + 1
    decode tokens, slot 1 3 prompt tokens and then 1 + 1 + 1: 14 of 32
    lanes; the decode chunk after it, 4 steps x 2 slots x 1 lane, all 8.
    The ids ride the chunk's spans: `steps` and `width` on
    `serve.dispatch`, `lanes` and `lanes_valid` on `serve.harvest`."""
    from paddle_tpu.inference import ContinuousBatcher
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(11)
    model = LlamaForCausalLM(LlamaConfig(**dict(
        _SERVE_SMALL, **SERVE_MODELS["dense_mha"])))
    model.eval()
    bat = ContinuousBatcher(model, max_batch_size=2, max_len=32, chunk=4,
                            prefill_chunk=4, admit_steps=4)
    rng = np.random.RandomState(5)
    bat.submit(_prompt(rng, 6), 12)
    bat.submit(_prompt(rng, 3), 12)
    sink = telemetry.add_sink(telemetry.MemorySink())
    try:
        bat.step()
        assert (bat.stats()["lanes"], bat.stats()["lanes_valid"]) == (32, 14)
        bat.step()
    finally:
        telemetry.remove_sink(sink)
    got = bat.stats()
    assert (got["lanes"], got["lanes_valid"]) == (40, 22)
    assert got["lanes_valid"] == got["prefill_tokens"] + got["decode_tokens"]
    dispatch = [(r["kind"], r["steps"], r["width"]) for r in sink.records
                if r["event"] == "serve.dispatch"]
    harvest = [(r["lanes"], r["lanes_valid"]) for r in sink.records
               if r["event"] == "serve.harvest"]
    assert dispatch == [("admit", 4, 4), ("decode", 4, 1)]
    assert harvest == [(32, 14), (8, 8)]
