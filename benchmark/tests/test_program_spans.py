"""program_spans.py on a small made-up trace (an XSpace text proto, as
test_trace_reduce.py reads a recorded one): one chip with six operations and
two idle intervals, a host thread with two `serve.step`s, another with a
`train.step`.  Times in microseconds:

    device   fusion.1 0-100 | idle 100-160 | fusion.2 160-260, reshape.3
             260-300 | idle 300-340 | fused_adamw.4 340-400, copy.5 400-410,
             while.6 410-500 around select.7 420-450
    host     serve.step 50-330 {device_wait 60-110, harvest 110-150 {deliver
             120-140}, evict 150-155, evict 300-310}, nothing 330-332,
             serve.step 332-480 {admit 332-336, dispatch 336-345,
             device_wait 345-470}
"""
import os

import pytest

import tiny
import harness
import program_spans
import trace_reduce

US = 1_000_000          # picoseconds


def _events(rows):
    return "\n".join(
        f"    events {{ metadata_id: {m} offset_ps: {a * US} "
        f"duration_ps: {(b - a) * US}{stats} }}" for m, a, b, stats in rows)


def _stat(key, value):
    kind = "int64_value" if isinstance(value, int) else "str_value"
    value = value if isinstance(value, int) else f'"{value}"'
    return f" stats {{ metadata_id: {key} {kind}: {value} }}"


DEVICE = [  # metadata id, name, start, end, op_name
    (1, "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p)", 0, 100,
     "jit(step)/jvp(llama.layer0)/mlp/dot_general:"),
    (2, "%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %q)", 160, 260,
     "jit(step)/transpose(jvp(llama.layer0))/mlp/dot_general:"),
    (3, "%reshape.3 = f32[8,1]{1,0} reshape(f32[8]{0} %g)", 260, 300,
     "jit(step)/train.optimizer/reshape:"),
    (4, "%fused_adamw.4 = f32[8]{0} custom-call(f32[8]{0} %r)", 340, 400,
     None),                 # its op_name is a REFERENCE to a stat's name
    (5, "%copy.5 = f32[8]{0} copy(f32[8]{0} %s)", 400, 410, ""),
    (6, "%while.6 = (s32[]) while((s32[]) %t)", 410, 500,
     "jit(step)/while:"),
    (7, "%select.7 = f32[8]{0} select(pred[] %ok, f32[8]{0} %n)", 420, 450,
     "jit(step)/train.guard/select_n:"),
]
HOST = [    # metadata id, start, end, stats
    (1, 50, 330, _stat(1, 1)), (2, 60, 110, _stat(1, 1) + _stat(2, "decode")),
    (3, 110, 150, _stat(1, 1)), (4, 120, 140, _stat(3, 24)),
    (5, 150, 155, ""), (5, 300, 310, ""),
    (1, 332, 480, _stat(1, 2)), (6, 332, 336, _stat(4, 1)),
    (7, 336, 345, _stat(1, 2) + _stat(2, "admit")),
    (2, 345, 470, _stat(1, 2) + _stat(2, "admit")),
    (8, 100, 200, ""),                          # not the program's
]
HOST_NAMES = {1: "serve.step", 2: "serve.device_wait", 3: "serve.harvest",
              4: "serve.deliver", 5: "serve.evict", 6: "serve.admit",
              7: "serve.dispatch", 8: "PjitFunction(serve_step)",
              9: "train.step", 10: "train.prepare", 11: "train.dispatch"}
TRAINER = [  # a second thread: children that overlap each other
    (9, 600, 700, _stat(5, 7) + _stat(6, 1)), (10, 610, 640, ""),
    (11, 630, 660, "")]


def _device_metadata():
    out = []
    for m, name, _, _, op in DEVICE:
        stat = "" if op == "" else (
            " stats { metadata_id: 1 ref_value: 2 }" if op is None
            else f' stats {{ metadata_id: 1 str_value: "{op}" }}')
        out.append(f'  event_metadata {{ key: {m} value {{ id: {m} '
                   f'name: "{name}"{stat} }} }}')
    return "\n".join(out)


HOST_METADATA = "\n".join(
    f'  event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}'
    for k, v in HOST_NAMES.items())

TEXT = f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{
    id: 1
    name: "XLA Ops"
{_events((m, a, b, "") for m, _, a, b, _ in DEVICE)}
  }}
  lines {{
    id: 2
    name: "XLA Modules"
{_events([(6, 0, 500, "")])}
  }}
{_device_metadata()}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 2 value {{ id: 2
    name: "jit(step)/train.optimizer/fused_adamw/pallas_call:" }} }}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{
    id: 1
    name: "python3"
{_events(HOST)}
  }}
  lines {{
    id: 2
    name: "trainer"
{_events(TRAINER)}
  }}
{HOST_METADATA}
  stat_metadata {{ key: 1 value {{ id: 1 name: "chunk" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "kind" }} }}
  stat_metadata {{ key: 3 value {{ id: 3 name: "tokens" }} }}
  stat_metadata {{ key: 4 value {{ id: 4 name: "admitted" }} }}
  stat_metadata {{ key: 5 value {{ id: 5 name: "step" }} }}
  stat_metadata {{ key: 6 value {{ id: 6 name: "k" }} }}
}}
"""

SERVE_CELL = {"workload": {"config": "deepseek-llm-7b",
                           "traffic": "chat_closed_c24", "chips": 1}}


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.load(text_proto=TEXT)


@pytest.fixture(scope="module")
def program():
    return program_spans.load(text_proto=TEXT)


@pytest.fixture
def found(monkeypatch, program):
    """The readers find `program` as the cell's trace."""
    monkeypatch.setattr(program_spans, "for_cell",
                        lambda trace, cell: program if trace else None)


def test_spans_nest_by_time_on_their_thread_and_keep_their_ids(program):
    spans = program.spans
    assert [s.name for s in spans if s.parent is None] == [
        "serve.step", "serve.step", "train.step"]
    by = {(s.name, s.start_ns): s for s in spans}
    first = spans.index(by["serve.step", 50e3])
    harvest = spans.index(by["serve.harvest", 110e3])
    assert by["serve.deliver", 120e3].parent == harvest
    assert by["serve.harvest", 110e3].parent == first
    assert by["serve.evict", 300e3].parent == first
    assert by["serve.admit", 332e3].parent == \
        spans.index(by["serve.step", 332e3])
    assert by["serve.step", 50e3].ids == {"chunk": 1}
    assert by["serve.dispatch", 336e3].ids == {"chunk": 2, "kind": "admit"}
    assert by["serve.deliver", 120e3].ids == {"tokens": 24}
    assert by["train.step", 600e3].ids == {"step": 7, "k": 1}
    assert not any(s.name.startswith("Pjit") for s in spans)


def test_self_time_takes_the_union_of_overlapping_children(program):
    spans = program.spans
    call = next(i for i, s in enumerate(spans) if s.name == "train.step")
    # prepare 610-640 and dispatch 630-660 lie both in the call and overlap:
    # 50 us are covered, not 60
    assert {s.name for s in spans if s.parent == call} == {
        "train.prepare", "train.dispatch"}
    assert program_spans.self_ns(program, call) == pytest.approx(50e3)
    first = next(i for i, s in enumerate(spans) if s.name == "serve.step")
    # 280 less device_wait 50, harvest 40 (deliver lies in it), evicts 5, 10
    assert program_spans.self_ns(program, first) == pytest.approx(175e3)
    harvest = next(i for i, s in enumerate(spans)
                   if s.name == "serve.harvest")
    assert program_spans.self_ns(program, harvest) == pytest.approx(20e3)
    assert program_spans.durations_ms(program, "serve.step") == [
        pytest.approx(0.280), pytest.approx(0.148)]


def test_an_idle_interval_is_cut_where_a_span_starts_or_ends(trace, program):
    idle = program_spans.idle_by_span(trace, program)
    # 100-160: device_wait until 110, harvest until 120 and from 140,
    # deliver 120-140, evict 150-155, the step itself 155-160
    # 300-340: evict until 310, the step until 330, no span until 332, admit
    # until 336, dispatch until 340
    assert {k: v * 1e-3 for k, v in idle.items()} == pytest.approx({
        "serve.device_wait": 10, "serve.harvest": 20, "serve.deliver": 20,
        "serve.evict": 15, "serve.step": 25, None: 2, "serve.admit": 4,
        "serve.dispatch": 4})
    assert sum(idle.values()) * 1e-9 == pytest.approx(
        trace_reduce.window_seconds(trace) - trace_reduce.busy_seconds(trace))


def test_the_four_idle_shares_add_up_to_the_idle_share(trace, found):
    parts = {p: program_spans.serve_idle_share(trace, SERVE_CELL, p)
             for p in ("harvest", "admit", "dispatch", "other")}
    assert parts == pytest.approx({"harvest": 8.0, "admit": 3.8,
                                   "dispatch": 0.8, "other": 7.4})
    assert sum(parts.values()) == pytest.approx(
        100.0 * trace_reduce.idle_share(trace)) == pytest.approx(20.0)
    # the step less the wait in it: 280 - 50 and 148 - 125
    assert program_spans.serve_host_self_ms_p50(trace, SERVE_CELL) == \
        pytest.approx((0.230 + 0.023) / 2)
    assert program_spans.span_ms_p50(trace, SERVE_CELL, "train.step") == \
        pytest.approx(0.100)


def test_device_time_by_scope(trace, program, found):
    # leaves: the while loop's event spans select.7 and is none
    assert sorted(program.device_leaves) == sorted([
        ("jit(step)/jvp(llama.layer0)/mlp/dot_general:", 100e3),
        ("jit(step)/transpose(jvp(llama.layer0))/mlp/dot_general:", 100e3),
        ("jit(step)/train.optimizer/reshape:", 40e3),
        ("jit(step)/train.optimizer/fused_adamw/pallas_call:", 60e3),
        ("", 10e3), ("jit(step)/train.guard/select_n:", 30e3)])
    shares = {p: program_spans.train_device_share(trace, SERVE_CELL, p)
              for p in ("forward", "backward", "optimizer")}
    # of 400 us busy: forward 100 + the copy without a name 10, backward
    # 100, optimizer 40 + 60 + the guard's 30; the loop's own 60 are no leaf
    assert shares == pytest.approx({"forward": 27.5, "backward": 25.0,
                                    "optimizer": 32.5})


def test_a_program_without_spans_or_scopes_reads_none_not_zero(monkeypatch):
    with open(os.path.join(tiny.HERE, "data",
                           "train_trace_start.xspace.txt")) as f:
        text = f.read()
    old_trace = trace_reduce.load(text_proto=text)
    old = program_spans.load(text_proto=text)
    assert old.spans == [] and old.device_leaves
    assert all(name == "" for name, _ in old.device_leaves)
    monkeypatch.setattr(program_spans, "for_cell", lambda trace, cell: old)
    for part in ("harvest", "admit", "dispatch", "other"):
        assert program_spans.serve_idle_share(old_trace, SERVE_CELL,
                                              part) is None
    for part in ("forward", "backward", "optimizer"):
        assert program_spans.train_device_share(old_trace, SERVE_CELL,
                                                part) is None
    assert program_spans.serve_host_self_ms_p50(old_trace, SERVE_CELL) is None
    assert program_spans.span_ms_p50(old_trace, SERVE_CELL,
                                     "train.step") is None


def test_the_cells_trace_is_found_by_what_the_workload_file_says(
        monkeypatch, tmp_path, trace):
    from jax.profiler import ProfileData
    monkeypatch.setattr(harness, "TRACE_ROOT", str(tmp_path))
    assert program_spans.for_cell(None, SERVE_CELL) is None    # untraced
    assert program_spans.for_cell(trace, SERVE_CELL) is None   # no file
    where = tmp_path / "dsllm7b_serve_chat_c24" / "plugins" / "profile" / "t"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(TEXT))
    got = program_spans.for_cell(trace, SERVE_CELL)
    assert len(got.spans) == 13
    assert program_spans.for_cell(trace, SERVE_CELL) is got     # parsed once
    stranger = {"workload": {"config": "tiny", "traffic": "none",
                             "chips": 1}}
    assert program_spans.for_cell(trace, stranger) is None
    # and the readers, through the metric files BENCHMARK.json names
    import run as runmod
    cell = dict(SERVE_CELL)
    assert runmod.metric_reader("serve_idle_share.admit").read(
        trace, {}, cell) == pytest.approx(3.8)
    assert runmod.metric_reader("serve_host_self_ms_p50").read(
        trace, {}, cell) == pytest.approx(0.1265)
    assert runmod.metric_reader("train_device_share.optimizer").read(
        trace, {}, cell) == pytest.approx(32.5)
    assert runmod.metric_reader("train_host_call_ms_p50").read(
        trace, {}, cell) == pytest.approx(0.100)
    assert runmod.metric_reader("serve_idle_share.other").read(
        None, {}, cell) is None
