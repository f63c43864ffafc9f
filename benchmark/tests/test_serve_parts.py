"""metrics/_serve_parts.py on a small made-up trace: two serve chunks of one
chip, five scan steps in all, one leaf operation or more of every part.
Times in microseconds:

    device   chunk 1, a while loop 0-90 around: embed 0-10, attn.project
             10-30, attn.window/attn.cache_write 30-40,
             attn.window/attn.attend 40-55, a layer's norm 55-60, mlp 60-90
             | idle 90-100 |
             chunk 2: ragged-dot-none 100-140, moe.combine 140-150,
             llama.lm_head 150-170, serve.sample 170-175, copy 175-180
    host     serve.dispatch (steps 4, width 32), serve.harvest (lanes 256,
             lanes_valid 40); serve.dispatch (steps 1, width 32),
             serve.harvest (lanes 64, lanes_valid 24)

Busy 170: projections 20, attention 25, mlp 30, experts 50, head 25, other 20
(the embedding, the norm, the copy)."""
import pytest

import tiny  # noqa: F401  (the benchmark's paths)
import program_spans
import run as runmod
import trace_reduce
from metrics import _serve_parts

US = 1_000_000          # picoseconds
SERVE_CELL = {"workload": {"config": "deepseek-llm-7b",
                           "traffic": "chat_closed_c24", "chips": 1}}


def _events(rows):
    return "\n".join(
        f"    events {{ metadata_id: {m} offset_ps: {a * US} "
        f"duration_ps: {(b - a) * US}{stats} }}" for m, a, b, stats in rows)


def _stat(key, value):
    kind = "int64_value" if isinstance(value, int) else "str_value"
    value = value if isinstance(value, int) else f'"{value}"'
    return f" stats {{ metadata_id: {key} {kind}: {value} }}"

LAYER = "jit(serve_step)/while/body/closed_call/llama.layer0/"
DEVICE = [  # name, start, end, op_name
    ("%while.1 = (s32[]) while((s32[]) %t)", 0, 90, "jit(serve_step)/while"),
    ("%fusion.2 = bf16[8]{0} fusion(s32[8]{0} %i)", 0, 10,
     "jit(serve_step)/while/body/closed_call/llama.embed/gather"),
    ("%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %x)", 10, 30,
     LAYER + "attn.project/dot_general"),
    ("%fusion.4 = bf16[8]{0} fusion(bf16[8]{0} %k)", 30, 40,
     LAYER + "attn.window/attn.cache_write/scatter"),
    ("%paged_attention.5 = bf16[8]{0} custom-call(bf16[8]{0} %q)", 40, 55,
     LAYER + "attn.window/attn.attend/jit(_call)/paged_attention/pallas_call"),
    ("%rms_norm_fwd.6 = bf16[8]{0} custom-call(bf16[8]{0} %h)", 55, 60,
     LAYER + "rms_norm_fwd/pallas_call"),
    ("%fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %h)", 60, 90,
     LAYER + "mlp/dot_general"),
    ("%ragged-dot-none.8 = bf16[8]{0} custom-call(bf16[8]{0} %r)", 100, 140,
     "ragged-dot-none"),      # the kernel's op_name is its own name
    ("%fusion.9 = bf16[8]{0} fusion(bf16[8]{0} %y)", 140, 150,
     LAYER + "moe.combine/mlp/reduce_sum"),
    ("%fusion.10 = bf16[8]{0} fusion(bf16[8]{0} %z)", 150, 170,
     "jit(serve_step)/while/body/closed_call/llama.lm_head/dot_general"),
    ("%fusion.11 = s32[2]{0} fusion(bf16[8]{0} %l)", 170, 175,
     "jit(serve_step)/while/body/closed_call/serve.sample/argmax"),
    ("%copy.12 = bf16[8]{0} copy(bf16[8]{0} %c)", 175, 180, ""),
]
EXPECTED_US = {"projections": 20, "attention": 25, "mlp": 30, "experts": 50,
               "head": 25, "other": 20}
STEPS = 5


def _text(with_ids=True):
    stats = {1: "kind", 2: "steps", 3: "width", 4: "lanes", 5: "lanes_valid"}

    def ids(*pairs):
        return "".join(_stat(k, v) for k, v in pairs) if with_ids else ""
    host = [(1, 0, 20, _stat(1, "admit") + ids((2, 4), (3, 32))),
            (2, 90, 95, ids((4, 256), (5, 40))),
            (1, 95, 100, _stat(1, "admit") + ids((2, 1), (3, 32))),
            (2, 180, 185, ids((4, 64), (5, 24)))]
    device_meta = "\n".join(
        f'  event_metadata {{ key: {m} value {{ id: {m} name: "{name}"'
        + (f' stats {{ metadata_id: 1 str_value: "{op}" }}' if op else "")
        + " } }" for m, (name, _, _, op) in enumerate(DEVICE, 1))
    host_meta = "\n".join(
        f'  event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}'
        for k, v in {1: "serve.dispatch", 2: "serve.harvest"}.items())
    stat_meta = "\n".join(
        f'  stat_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}'
        for k, v in stats.items())
    return f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{
    id: 1
    name: "XLA Ops"
{_events((m, a, b, "") for m, (_, a, b, _) in enumerate(DEVICE, 1))}
  }}
{device_meta}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{
    id: 1
    name: "python3"
{_events(host)}
  }}
{host_meta}
{stat_meta}
}}
"""


@pytest.fixture(scope="module", params=[True, False], ids=["ids", "parent"])
def traced(request):
    text = _text(request.param)
    return (request.param, trace_reduce.load(text_proto=text),
            program_spans.load(text_proto=text))


@pytest.fixture
def found(monkeypatch, traced):
    monkeypatch.setattr(program_spans, "for_cell",
                        lambda trace, cell: traced[2] if trace else None)
    return traced


def test_the_six_parts_sum_to_busy_time_a_step(found):
    with_ids, trace, _ = found
    got = {part: runmod.metric_reader(f"serve_step_device_ms.{part}").read(
        trace, {}, SERVE_CELL) for part in EXPECTED_US}
    if not with_ids:
        # the parent's program: no `steps` on its dispatch spans
        assert got == dict.fromkeys(EXPECTED_US)
        return
    assert got == pytest.approx({p: us * 1e-3 / STEPS
                                 for p, us in EXPECTED_US.items()})
    assert sum(got.values()) == pytest.approx(
        trace_reduce.busy_seconds(trace) * 1e3 / STEPS)


def test_the_first_matching_part_takes_an_operation_once(found):
    _, trace, program = found
    # attn.window/attn.attend and moe.combine/mlp each count once: as
    # attention and as experts
    ns = _serve_parts.device_ns(trace, program)
    assert ns["attention"] * 1e-3 == pytest.approx(25)
    assert ns["experts"] * 1e-3 == pytest.approx(50)
    assert ns["mlp"] * 1e-3 == pytest.approx(30)
    assert sum(ns.values()) * 1e-9 == pytest.approx(
        trace_reduce.busy_seconds(trace))


def test_lane_valid_share_reads_the_harvest_ids(found):
    with_ids, trace, _ = found
    share = runmod.metric_reader("serve_lane_valid_share").read(
        trace, {}, SERVE_CELL)
    assert share == (pytest.approx(100.0 * 64 / 320) if with_ids else None)


def test_nothing_to_read_is_none_not_zero():
    for part in EXPECTED_US:
        assert runmod.metric_reader(f"serve_step_device_ms.{part}").read(
            None, {}, SERVE_CELL) is None
    assert runmod.metric_reader("serve_lane_valid_share").read(
        None, {}, SERVE_CELL) is None
