"""Telemetry step-log report CLI — the command-line face of
paddle_tpu.telemetry (JSON output option + --selftest wired into
tier-1, like tools/verify_program.py).

    python tools/telemetry_report.py steps.jsonl [--json]
        Read a JSONL step log (telemetry.attach_jsonl) and print:
        step-time medians/p99 over warm train.step events, tokens/s,
        the program's spans (count, duration and self time per name:
        train.step and its children, serve.step and its phases),
        serving chunk stats with each chunk's time by phase, io
        host-wait stats, and the compile-cache hit rate.

    python tools/telemetry_report.py --selftest
        CI canary: runs a 5-step toy train loop with a JSONL sink (and
        a compile cache dir) in a temp dir, validates the emitted
        schema (every step event carries wall_ms and step_ms;
        compile.program events carry hit/miss), THEN a tiny
        serve workload that load-sheds (bounded queue) and misses a
        deadline, validating the serve-robustness events
        (serve.shed carries slo+reason, serve.deadline_miss fires)
        and their report section.  Exit 1 on any violation — a
        silently empty telemetry plane is exactly the failure mode
        this guards.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _pct(xs, q):
    # ONE percentile derivation for the whole plane: the registry's
    # (what Histogram.percentiles and stats() blocks use) — the report
    # no longer re-derives its own convention from raw dumps
    from paddle_tpu.telemetry import percentile_of
    return percentile_of(xs, q)


def load_events(path):
    events = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                events.append(json.loads(line))
            except ValueError as e:
                raise SystemExit(f"{path}:{i + 1}: not a JSON object "
                                 f"({e})")
    return events


def analyze(events):
    """Aggregate a JSONL event list into the report dict."""
    steps = [e for e in events if e.get("event") == "train.step"]
    warm = [e for e in steps if not e.get("cold")]
    out = {"events": len(events), "train_steps": len(steps),
           "cold_steps": len(steps) - len(warm)}

    def series(key):
        return [e[key] for e in warm if isinstance(e.get(key),
                                                   (int, float))]

    if warm:
        walls = series("step_ms")
        # the shared summary derivation (ISSUE 14) adds TRUE window
        # min/max beside the percentiles — the outliers a percentile
        # window samples away are what an incident hunt needs
        from paddle_tpu.telemetry import summary_of
        s = summary_of(walls)
        out["step_ms"] = {"p50": round(s["p50"], 3),
                          "p99": round(s["p99"], 3),
                          "min": round(s["min"], 3),
                          "max": round(s["max"], 3)}
        tps = series("tokens_per_sec")
        if tps:
            out["tokens_per_sec"] = {"p50": round(_pct(tps, 50), 1),
                                     "p99": round(_pct(tps, 99), 1)}

    # the program's spans (telemetry.span records: `span` is the
    # record's running number on its thread, `parent_span` that of the
    # span it lay in): per name the duration and the SELF time, which
    # is the duration minus what the span's children cover
    spans = [e for e in events if "span" in e
             and isinstance(e.get("dur_ms"), (int, float))]
    if spans:
        covered = {}
        for e in spans:
            if "parent_span" in e:
                key = (e.get("rank"), e["parent_span"], e.get("parent"))
                covered[key] = covered.get(key, 0.0) + e["dur_ms"]
        by_name = {}
        for e in spans:
            own = e["dur_ms"] - covered.get(
                (e.get("rank"), e["span"], e["event"]), 0.0)
            by_name.setdefault(e["event"], []).append(
                (e["dur_ms"], max(own, 0.0)))
        out["spans"] = {
            name: {"count": len(v),
                   "p50_ms": round(_pct([d for d, _ in v], 50), 3),
                   "max_ms": round(max(d for d, _ in v), 3),
                   "self_p50_ms": round(_pct([o for _, o in v], 50), 3),
                   "self_max_ms": round(max(o for _, o in v), 3)}
            for name, v in sorted(by_name.items())}

    compiles = [e for e in events if e.get("event") == "compile.program"]
    if compiles:
        hits = sum(1 for e in compiles if e.get("cache") == "hit")
        judged = sum(1 for e in compiles
                     if e.get("cache") in ("hit", "miss"))
        out["compile"] = {
            "programs": len(compiles), "hits": hits,
            "hit_rate": round(hits / judged, 3) if judged else None,
            "trace_ms": round(sum(e.get("trace_ms", 0.0)
                                  for e in compiles), 1),
            "compile_ms": round(sum(e.get("compile_ms", 0.0)
                                    for e in compiles), 1),
        }

    chunks = [e for e in events if e.get("event") == "serve.chunk"]
    if chunks:
        cw = [e["wall_ms"] for e in chunks if not e.get("first_use")]
        out["serve"] = {
            "chunks": len(chunks),
            "chunk_ms_p50": round(_pct(cw, 50), 3),
            "chunk_ms_p99": round(_pct(cw, 99), 3),
            "prefill_tokens": sum(e.get("prefill_tokens", 0)
                                  for e in chunks),
            "decode_tokens": sum(e.get("decode_tokens", 0)
                                 for e in chunks),
            "recompiles": sum(1 for e in events
                              if e.get("event") == "serve.recompile"),
        }
        # each steady chunk's step() by phase (the six <phase>_ms of
        # serve.chunk): was a slow stretch the host's or the device's
        phases = {}
        for e in chunks:
            if e.get("first_use"):
                continue
            for k, v in e.items():
                if k.endswith("_ms") and k != "wall_ms" \
                        and isinstance(v, (int, float)):
                    phases.setdefault(k[:-3], []).append(v)
        if phases:
            out["serve"]["phase_ms"] = {
                k: {"p50": round(_pct(v, 50), 3), "max": round(max(v), 3)}
                for k, v in phases.items()}
        # paged-KV pool trajectory (serve.kv rides every chunk): last
        # snapshot carries the lifetime counters, peak shows pressure
        kv = [e for e in events if e.get("event") == "serve.kv"]
        if kv:
            last = kv[-1]
            out["serve"]["kv"] = {
                "pages": last.get("pages", 0),
                "pages_used_peak": max(e.get("pages_used", 0)
                                       for e in kv),
                "pages_cached": last.get("pages_cached", 0),
                "prefix_hit_tokens": last.get("prefix_hit_tokens", 0),
                "evictions": last.get("evictions", 0),
                "kv_bytes": last.get("kv_bytes", 0),
            }
    # serve-robustness events (ISSUE 9: SLO shedding, deadline misses,
    # faulted-slot requeues, hung chunks, drain) — reported whenever
    # any occurred, even on a log with no serve.chunk events (a drain
    # can fire before the first chunk)
    shed = [e for e in events if e.get("event") == "serve.shed"]
    rob = {
        "shed": len(shed),
        "shed_by_class": {},
        "shed_by_reason": {},
        "deadline_misses": sum(1 for e in events
                               if e.get("event")
                               == "serve.deadline_miss"),
        "requeues": sum(1 for e in events
                        if e.get("event") == "serve.requeue"),
        "chunk_faults": sum(1 for e in events
                            if e.get("event") == "serve.chunk_fault"),
        "hung_chunks": sum(1 for e in events
                           if e.get("event") == "serve.hung"),
        "drains": sum(1 for e in events
                      if e.get("event") == "serve.drain"
                      and e.get("phase") == "begin"),
    }
    for e in shed:
        for key, field in (("shed_by_class", "slo"),
                           ("shed_by_reason", "reason")):
            v = str(e.get(field))
            rob[key][v] = rob[key].get(v, 0) + 1
    if any(v for k, v in rob.items() if not k.startswith("shed_by")):
        out.setdefault("serve", {})["robustness"] = rob

    # speculative decoding (ISSUE 11): accept-rate + accepted-per-step
    # from the per-chunk serve.spec events.  accepted_per_step (=
    # accepted drafts + the bonus token) is reconstructed per chunk as
    # its mean; p50/p99 over chunks describe the burst distribution
    spec_ev = [e for e in events if e.get("event") == "serve.spec"]
    if spec_ev:
        drafted = sum(e.get("drafted", 0) for e in spec_ev)
        accepted = sum(e.get("accepted", 0) for e in spec_ev)
        steps = sum(e.get("steps", 0) for e in spec_ev)
        per_step = [(e["accepted"] + e["steps"]) / e["steps"]
                    for e in spec_ev if e.get("steps")]
        out.setdefault("serve", {})["speculation"] = {
            "chunks": len(spec_ev),
            "drafted": drafted,
            "accepted": accepted,
            "accept_rate": round(accepted / drafted, 4) if drafted
            else 0.0,
            "accepted_per_step_p50": round(_pct(per_step, 50), 3),
            "accepted_per_step_p99": round(_pct(per_step, 99), 3),
            "verify_steps": steps,
        }

    # serve-fleet router (ISSUE 15): per-replica routed/requeued
    # counts, the prefix-route hit rate (routes whose chosen replica
    # held a resident prefix) and the router's decision-time
    # percentiles, from the router.* events ServeRouter emits
    routes = [e for e in events if e.get("event") == "router.route"]
    rreq = [e for e in events if e.get("event") == "router.requeue"]
    rkill = [e for e in events if e.get("event") == "router.kill"]
    rdrain = [e for e in events if e.get("event") == "router.drain"]
    rshed = [e for e in events if e.get("event") == "router.shed"]
    rreb = [e for e in events if e.get("event") == "router.rebalance"]
    if routes or rreq or rkill or rdrain:
        routed_by, hit, dec = {}, 0, []
        for e in routes:
            r = str(e.get("replica"))
            routed_by[r] = routed_by.get(r, 0) + 1
            if (e.get("prefix_hit") or 0) > 0:
                hit += 1
            if isinstance(e.get("decision_ms"), (int, float)):
                dec.append(e["decision_ms"])
        req_by = {}
        for e in rreq:
            r = str(e.get("to"))
            req_by[r] = req_by.get(r, 0) + 1
        fleet = {
            "routed": len(routes),
            "routed_by_replica": routed_by,
            "prefix_route_hit_rate": round(hit / len(routes), 4)
            if routes else 0.0,
            "requeues": len(rreq),
            "requeued_by_replica": req_by,
            "kills": len(rkill),
            "drains": len(rdrain),
            "shed": len(rshed),
            "rebalances": sum(e.get("moved", 1) for e in rreb),
        }
        if dec:
            fleet["decision_ms_p50"] = round(_pct(dec, 50), 4)
            fleet["decision_ms_p99"] = round(_pct(dec, 99), 4)
        out.setdefault("serve", {})["fleet"] = fleet

    # disaggregated hand-off plane (ISSUE 20): prefill->decode page
    # streams (serve.handoff export/import pairs), the router's
    # end-to-end hand-off latency, and the cross-replica dedup rate
    # (pages the decode side did NOT rewrite because its trie already
    # held them), plus prefix replication traffic (router.replicate)
    hoff = [e for e in events if e.get("event") == "serve.handoff"]
    rhoff = [e for e in events if e.get("event") == "router.handoff"]
    repl = [e for e in events if e.get("event") == "router.replicate"]
    if hoff or rhoff or repl:
        exp = [e for e in hoff if e.get("dir") == "export"]
        imp = [e for e in hoff if e.get("dir") == "import"]
        pages_in = sum(int(e.get("pages") or 0) for e in imp)
        dedup = sum(int(e.get("dedup_pages") or 0) for e in imp)
        h = {
            "exports": len(exp),
            "imports": len(imp),
            "bytes": sum(int(e.get("bytes") or 0) for e in exp),
            "pages": sum(int(e.get("pages") or 0) for e in exp),
            "dedup_pages": dedup,
            "dedup_rate": round(dedup / pages_in, 4)
            if pages_in else 0.0,
            "replicated_pages": sum(int(e.get("pages") or 0)
                                    for e in repl),
        }
        ms = [e["ms"] for e in rhoff
              if isinstance(e.get("ms"), (int, float))]
        if ms:
            h["ms_p50"] = round(_pct(ms, 50), 4)
            h["ms_p99"] = round(_pct(ms, 99), 4)
        out.setdefault("serve", {})["handoff"] = h

    # per-request latency spans (ISSUE 10): queue/TTFT/TPOT/e2e
    # percentiles + per-SLO-class deadline attainment from the
    # serve.request events the batcher emits per delivered request
    reqs = [e for e in events if e.get("event") == "serve.request"]
    if reqs:
        from paddle_tpu.telemetry import summary_of
        lat = {}
        for k in ("queue_ms", "ttft_ms", "tpot_ms", "e2e_ms"):
            vals = [e[k] for e in reqs
                    if isinstance(e.get(k), (int, float))]
            if vals:
                s = summary_of(vals)
                lat[k] = {"count": s["count"],
                          "p50": round(s["p50"], 3),
                          "p99": round(s["p99"], 3),
                          "min": round(s["min"], 3),
                          "max": round(s["max"], 3)}
        att = {}
        for e in reqs:
            a = att.setdefault(str(e.get("slo")),
                               {"requests": 0, "with_deadline": 0,
                                "deadline_met": 0})
            a["requests"] += 1
            if "deadline_met" in e:
                a["with_deadline"] += 1
                a["deadline_met"] += bool(e["deadline_met"])
        for a in att.values():
            if a["with_deadline"]:
                a["attainment"] = round(
                    a["deadline_met"] / a["with_deadline"], 4)
        # in chunks (the `chunk` ids of the serve.step spans that
        # caused them): admission to first token, first token to done
        for name, a, b in (("prefill_chunks", "admit_chunk",
                            "first_token_chunk"),
                           ("decode_chunks", "first_token_chunk",
                            "done_chunk")):
            vals = [e[b] - e[a] + 1 for e in reqs
                    if e.get(a, -1) >= 0 and e.get(b, -1) >= 0]
            if vals:
                lat[name] = {"count": len(vals),
                             "p50": _pct(vals, 50), "max": max(vals)}
        s = out.setdefault("serve", {})
        s["latency"] = lat
        s["slo"] = att

    # cost/roofline section (ISSUE 12): per-program FLOPs/bytes from
    # the cost.program records the ledger publishes on resolve, plus
    # any perf.drift events (predicted vs measured below the floor)
    cost_kinds = ("cost.program", "cost.measure", "perf.drift")
    if any(e.get("event") in cost_kinds for e in events):
        progs, n_drift = {}, 0
        # ONE pass in log order: the LATEST record per program wins —
        # cost.measure carries the drift STATE (perf.drift is the
        # edge-triggered alarm), so a recovered measure after a drift
        # episode clears the flag and a persisting one keeps it
        for e in events:
            kind = e.get("event")
            if kind not in cost_kinds:
                continue
            p = progs.setdefault(str(e.get("label")), {})
            if kind == "cost.program":
                p.update({k: e[k] for k in
                          ("flops", "bytes_accessed")
                          if isinstance(e.get(k), (int, float))})
                continue
            p["predicted_ms"] = e.get("predicted_ms")
            p["measured_ms"] = e.get("measured_ms")
            p["attained"] = e.get("attained")
            if kind == "perf.drift":
                n_drift += 1
                p["drift"] = True
            else:
                p["bound"] = e.get("bound")
                if e.get("drift"):
                    p["drift"] = True
                else:
                    p.pop("drift", None)
        out["cost"] = {"programs": progs, "drifts": n_drift}

    # numerics plane (ISSUE 14): grad-norm trend + nonfinite-step
    # attribution from the train.numerics events the flagged trainers
    # emit (and the train.anomaly triggers the guard/numerics publish)
    nums = [e for e in events if e.get("event") == "train.numerics"]
    if nums:
        def _gn(e):
            vals = [v for v in e.get("grad_norm", [])
                    if isinstance(v, (int, float))]
            return round(sum(v * v for v in vals) ** 0.5, 6) \
                if vals else None
        bad = [e for e in nums if e.get("first_nonfinite", -1) >= 0]
        out["numerics"] = {
            "samples": len(nums),
            "grad_norm_first": _gn(nums[0]),
            "grad_norm_last": _gn(nums[-1]),
            "nonfinite_steps": len(bad),
            "anomalies": sum(1 for e in events
                             if e.get("event") == "train.anomaly"),
        }
        if bad:
            out["numerics"]["first_nonfinite_layer"] = \
                bad[0].get("first_nonfinite_layer")

    io_steps = [e for e in events if e.get("event") == "io.step"]
    if io_steps:
        ws = [e.get("host_wait_ms", 0.0) for e in io_steps]
        out["io"] = {"steps": len(io_steps),
                     "host_wait_ms_p50": round(_pct(ws, 50), 3),
                     "host_wait_ms_p99": round(_pct(ws, 99), 3),
                     "cold_gets": sum(1 for e in io_steps
                                      if e.get("cold"))}

    for ev, key in (("watchdog.timeout", "watchdog_timeouts"),
                    ("fault.hit", "fault_hits"),
                    ("ckpt.commit", "ckpt_commits"),
                    ("ckpt.gc", "ckpt_gcs")):
        n = sum(1 for e in events if e.get("event") == ev)
        if n:
            out[key] = n
    return out


def render(rep):
    lines = [f"events: {rep['events']}  train steps: "
             f"{rep['train_steps']} ({rep['cold_steps']} cold, excluded)"]
    if "step_ms" in rep:
        lines.append(f"step ms     p50={rep['step_ms']['p50']:<10} "
                     f"p99={rep['step_ms']['p99']:<10} "
                     f"min={rep['step_ms'].get('min')} "
                     f"max={rep['step_ms'].get('max')}")
    if "numerics" in rep:
        n = rep["numerics"]
        line = (f"numerics    {n['samples']} samples, grad_norm "
                f"{n['grad_norm_first']} -> {n['grad_norm_last']}, "
                f"{n['nonfinite_steps']} nonfinite")
        if n.get("first_nonfinite_layer"):
            line += f" (first: {n['first_nonfinite_layer']})"
        lines.append(line)
    if "tokens_per_sec" in rep:
        lines.append(f"tokens/s    p50={rep['tokens_per_sec']['p50']}")
    for name, v in rep.get("spans", {}).items():
        lines.append(f"  span {name:<20} n={v['count']:<5} "
                     f"p50={v['p50_ms']}ms max={v['max_ms']}ms  self "
                     f"p50={v['self_p50_ms']}ms max={v['self_max_ms']}ms")
    if "compile" in rep:
        c = rep["compile"]
        rate = "n/a" if c["hit_rate"] is None else c["hit_rate"]
        lines.append(f"compile     {c['programs']} programs, hit rate "
                     f"{rate}, trace {c['trace_ms']}ms, "
                     f"compile {c['compile_ms']}ms")
    if "serve" in rep:
        s = rep["serve"]
        if "chunks" in s:
            lines.append(f"serve       {s['chunks']} chunks, p50 "
                         f"{s['chunk_ms_p50']}ms, prefill/decode "
                         f"{s['prefill_tokens']}/{s['decode_tokens']}, "
                         f"{s['recompiles']} recompiles")
        else:
            lines.append("serve       (no chunk events)")
        if "phase_ms" in s:
            lines.append("  phases    " + ", ".join(
                f"{k} p50={v['p50']}/max={v['max']}ms"
                for k, v in s["phase_ms"].items()))
        if "kv" in s:
            k = s["kv"]
            lines.append(
                f"  kv pool   {k['pages_used_peak']}/{k['pages']} "
                f"pages peak ({k['pages_cached']} cached), "
                f"prefix hits {k['prefix_hit_tokens']} tok, "
                f"{k['evictions']} evictions, "
                f"{k['kv_bytes'] / 1e6:.1f}MB")
        if "latency" in s:
            parts = []
            for k in ("ttft_ms", "tpot_ms", "e2e_ms", "queue_ms"):
                v = s["latency"].get(k)
                if v:
                    parts.append(f"{k[:-3]} p50={v['p50']}/"
                                 f"p99={v['p99']}ms")
            if parts:
                lines.append("  latency   " + ", ".join(parts))
        if "slo" in s:
            parts = []
            for cls, a in sorted(s["slo"].items()):
                att = a.get("attainment")
                parts.append(f"{cls}={a['requests']}"
                             + (f" (attain {att})" if att is not None
                                else ""))
            lines.append("  slo       " + ", ".join(parts))
        if "speculation" in s:
            sp = s["speculation"]
            lines.append(
                f"  spec      accept_rate {sp['accept_rate']} "
                f"({sp['accepted']}/{sp['drafted']} drafts over "
                f"{sp['verify_steps']} verify steps), "
                f"accepted/step p50={sp['accepted_per_step_p50']} "
                f"p99={sp['accepted_per_step_p99']}")
        if "fleet" in s:
            f = s["fleet"]
            by = ", ".join(f"r{k}={v}" for k, v
                           in sorted(f["routed_by_replica"].items()))
            line = (f"  fleet     routed {f['routed']}"
                    f"{' (' + by + ')' if by else ''}, prefix-hit "
                    f"{f['prefix_route_hit_rate']}, requeues "
                    f"{f['requeues']}, kills {f['kills']}, drains "
                    f"{f['drains']}, rebalances {f['rebalances']}")
            if "decision_ms_p50" in f:
                line += (f", decide p50={f['decision_ms_p50']}/"
                         f"p99={f['decision_ms_p99']}ms")
            lines.append(line)
        if "handoff" in s:
            h = s["handoff"]
            line = (f"  handoff   {h['exports']} exported / "
                    f"{h['imports']} imported, {h['pages']} pages "
                    f"({h['bytes'] / 1e6:.2f}MB), dedup "
                    f"{h['dedup_rate']}, replicated "
                    f"{h['replicated_pages']} pages")
            if "ms_p50" in h:
                line += (f", p50={h['ms_p50']}/"
                         f"p99={h['ms_p99']}ms")
            lines.append(line)
        if "robustness" in s:
            r = s["robustness"]
            by_cls = ", ".join(f"{c}={n}" for c, n
                               in sorted(r["shed_by_class"].items()))
            lines.append(
                f"  robust    shed {r['shed']}"
                f"{' (' + by_cls + ')' if by_cls else ''}, "
                f"deadline misses {r['deadline_misses']}, "
                f"requeues {r['requeues']}, "
                f"chunk faults {r['chunk_faults']}, "
                f"hung {r['hung_chunks']}, drains {r['drains']}")
    if "cost" in rep:
        c = rep["cost"]
        lines.append(f"cost        {len(c['programs'])} program(s), "
                     f"{c['drifts']} drift(s)")
        for lbl, p in sorted(c["programs"].items()):
            parts = []
            if "flops" in p:
                parts.append(f"{p['flops']:.3g} flops")
            if "bytes_accessed" in p:
                parts.append(f"{p['bytes_accessed']:.3g} B")
            if "bound" in p and p.get("bound"):
                parts.append(f"{p['bound']}-bound")
            if p.get("measured_ms") is not None:
                parts.append(
                    f"predicted {p.get('predicted_ms')}ms vs "
                    f"measured {p.get('measured_ms')}ms "
                    f"(attained {p.get('attained')})")
            if p.get("drift"):
                parts.append("DRIFT")
            lines.append(f"  {lbl:<24} " + ", ".join(parts))
    if "io" in rep:
        i = rep["io"]
        lines.append(f"io          {i['steps']} gets, host wait p50 "
                     f"{i['host_wait_ms_p50']}ms p99 "
                     f"{i['host_wait_ms_p99']}ms, {i['cold_gets']} cold")
    for k in ("watchdog_timeouts", "fault_hits", "ckpt_commits",
              "ckpt_gcs"):
        if k in rep:
            lines.append(f"{k}: {rep[k]}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# selftest

def _selftest():
    import tempfile
    problems = []
    with tempfile.TemporaryDirectory() as d:
        log = os.path.join(d, "steps.jsonl")
        from paddle_tpu.framework.flags import set_flags
        # arms the AOT store (in telemetry.cache_dir(), a few KB)
        set_flags({"FLAGS_compile_cache_dir": "1"})
        try:
            import paddle_tpu as paddle
            from paddle_tpu import telemetry
            from paddle_tpu.jit import TrainStep

            sink = telemetry.attach_jsonl(log)
            try:
                paddle.seed(0)
                model = paddle.nn.Sequential(
                    paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
                    paddle.nn.Linear(16, 8))
                opt = paddle.optimizer.AdamW(
                    1e-3, parameters=model.parameters())
                step = TrainStep(
                    model,
                    lambda o, y: paddle.nn.functional.mse_loss(o, y),
                    opt)
                rng = np.random.RandomState(0)
                x = paddle.to_tensor(rng.randn(4, 8).astype(np.float32))
                for _ in range(5):
                    step(x, x)
                # cost/roofline leg (ISSUE 12): resolving the ledger
                # with the sink live publishes cost.program records,
                # and a planted slow wall under FLAGS_mfu_floor must
                # surface as perf.drift
                telemetry.cost_report()
                set_flags({"FLAGS_mfu_floor": 0.95})
                try:
                    # explicit measured= makes the plant authoritative
                    # (a single observe() sample would drown in the
                    # median of the real warm walls)
                    telemetry.cost_report(
                        measured={"jit.TrainStep.step": 1e6})
                finally:
                    set_flags({"FLAGS_mfu_floor": 0.0})
                    # clear the drift edge state — the selftest must
                    # not leak its planted drift into the caller's
                    # ledger
                    telemetry.costledger.reset()
            finally:
                telemetry.remove_sink(sink)
        finally:
            set_flags({"FLAGS_compile_cache_dir": ""})

        events = load_events(log)
        steps = [e for e in events if e.get("event") == "train.step"]
        if len(steps) != 5:
            problems.append(f"expected 5 train.step events, got "
                            f"{len(steps)}")
        for i, e in enumerate(steps):
            for k in ("ts", "trainer", "step", "k", "wall_ms",
                      "step_ms"):
                if k not in e:
                    problems.append(f"step event {i} missing {k!r}")
            if e.get("wall_ms", -1) < 0:
                problems.append(f"step event {i} negative wall_ms")
        if [e["step"] for e in steps] != sorted(e["step"] for e in steps):
            problems.append("step counter not monotonic")
        compiles = [e for e in events
                    if e.get("event") == "compile.program"]
        if not compiles:
            problems.append("no compile.program events with "
                            "FLAGS_compile_cache_dir armed")
        for e in compiles:
            if e.get("cache") not in ("hit", "miss", "error"):
                problems.append(f"compile event bad cache field: {e}")
        cost_ev = [e for e in events
                   if e.get("event") == "cost.program"]
        if not any(e.get("label") == "jit.TrainStep.step"
                   and e.get("flops", 0) > 0
                   and e.get("bytes_accessed", 0) > 0
                   for e in cost_ev):
            problems.append(f"no cost.program record for the step "
                            f"program: {cost_ev}")
        meas_ev = [e for e in events
                   if e.get("event") == "cost.measure"]
        if not any(e.get("label") == "jit.TrainStep.step"
                   and isinstance(e.get("predicted_ms"), (int, float))
                   and isinstance(e.get("measured_ms"), (int, float))
                   and "attained" in e for e in meas_ev):
            problems.append(f"no predicted-vs-measured cost.measure "
                            f"record: {meas_ev}")
        drift_ev = [e for e in events if e.get("event") == "perf.drift"]
        if not drift_ev:
            problems.append("planted drift produced no perf.drift "
                            "event")
        for e in drift_ev:
            for key in ("label", "predicted_ms", "measured_ms",
                        "attained", "floor"):
                if key not in e:
                    problems.append(f"perf.drift missing {key!r}: {e}")
        rep = analyze(events)
        if "step_ms" not in rep:
            problems.append(f"report missing step stats: {rep}")
        cost = rep.get("cost")
        if not cost or cost.get("drifts", 0) < 1 \
                or "jit.TrainStep.step" not in cost.get("programs", {}):
            problems.append(f"report missing cost/roofline section: "
                            f"{rep.get('cost')}")
        print(render(rep))

        # serve-robustness leg (ISSUE 9): a bounded queue + a dead
        # deadline must surface as serve.shed / serve.deadline_miss
        # events and a serve "robustness" report section
        slog = os.path.join(d, "serve.jsonl")
        from paddle_tpu import telemetry
        import paddle_tpu as paddle
        from paddle_tpu.framework.flags import set_flags as _sf
        from paddle_tpu.inference import ContinuousBatcher
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        paddle.seed(13)
        cfg = llama_tiny_config(num_hidden_layers=1, hidden_size=32,
                                intermediate_size=64,
                                num_attention_heads=2,
                                num_key_value_heads=2, vocab_size=64)
        model = LlamaForCausalLM(cfg)
        rng = np.random.RandomState(2)
        sink = telemetry.attach_jsonl(slog)
        _sf({"FLAGS_serve_queue_depth": 2})
        try:
            bat = ContinuousBatcher(model, max_batch_size=1,
                                    max_len=32, chunk=4,
                                    prefill_chunk=4)
            bat.submit(rng.randint(1, 64, 4).astype(np.int32), 4,
                       slo="interactive")
            # queued past its deadline -> deadline miss at the next
            # boundary
            bat.submit(rng.randint(1, 64, 5).astype(np.int32), 4,
                       slo="batch", deadline_ms=0.001)
            bat.submit(rng.randint(1, 64, 6).astype(np.int32), 4,
                       slo="batch")
            # queue already at depth 2 -> lowest-SLO newest sheds
            bat.submit(rng.randint(1, 64, 4).astype(np.int32), 4,
                       slo="best_effort")
            bat.run()
        finally:
            _sf({"FLAGS_serve_queue_depth": 0})
            telemetry.remove_sink(sink)
        sevents = load_events(slog)
        sheds = [e for e in sevents if e.get("event") == "serve.shed"]
        if len(sheds) < 2:
            problems.append(f"expected >=2 serve.shed events, got "
                            f"{len(sheds)}")
        for e in sheds:
            for k in ("req", "slo", "reason"):
                if k not in e:
                    problems.append(f"serve.shed missing {k!r}: {e}")
        if not any(e.get("event") == "serve.deadline_miss"
                   for e in sevents):
            problems.append("no serve.deadline_miss event emitted")
        srep = analyze(sevents)
        rob = srep.get("serve", {}).get("robustness")
        if not rob:
            problems.append(f"report missing serve robustness "
                            f"section: {srep}")
        elif rob["shed"] != len(sheds) \
                or rob["deadline_misses"] < 1 \
                or "best_effort" not in rob["shed_by_class"]:
            problems.append(f"robustness section wrong: {rob}")
        # the program's spans and the chunk's phases (ISSUE 25): every
        # steady serve.chunk carries the six phase durations, and the
        # span table names serve.step with its children
        from paddle_tpu.inference.serving import PHASES
        for e in sevents:
            if e.get("event") == "serve.chunk":
                for k in PHASES:
                    if not isinstance(e.get(f"{k}_ms"), (int, float)):
                        problems.append(f"serve.chunk missing {k}_ms: {e}")
        want = {"serve.step"} | {f"serve.{k}" for k in PHASES}
        if not want <= set(srep.get("spans", {})):
            problems.append(f"span table lacks "
                            f"{sorted(want - set(srep.get('spans', {})))}")
        steady = [e for e in sevents if e.get("event") == "serve.chunk"
                  and not e.get("first_use")]
        if steady and set(srep["serve"].get("phase_ms", {})) \
                != set(PHASES):
            problems.append(f"report missing serve phase split: "
                            f"{srep.get('serve')}")
        print(render(srep))

        # speculative-decoding leg (ISSUE 11): a self-speculating
        # serve run must surface serve.spec events and a speculation
        # report section with a sane accept rate
        plog = os.path.join(d, "spec.jsonl")
        sink = telemetry.attach_jsonl(plog)
        try:
            bat = ContinuousBatcher(model, max_batch_size=1,
                                    max_len=32, chunk=4,
                                    prefill_chunk=4, spec_tokens=2,
                                    draft_model=model)
            bat.submit(rng.randint(1, 64, 5).astype(np.int32), 6)
            bat.run()
        finally:
            telemetry.remove_sink(sink)
        pevents = load_events(plog)
        spec_ev = [e for e in pevents if e.get("event") == "serve.spec"]
        if not spec_ev:
            problems.append("no serve.spec events emitted under "
                            "speculation")
        prep = analyze(pevents)
        spec = prep.get("serve", {}).get("speculation")
        if not spec:
            problems.append(f"report missing speculation section: "
                            f"{prep}")
        elif not (0.0 < spec["accept_rate"] <= 1.0
                  and spec["drafted"] > 0
                  and spec["accepted_per_step_p50"] > 1.0):
            problems.append(f"speculation section wrong: {spec}")
        print(render(prep))

        # serve-fleet router leg (ISSUE 15): a 2-replica staggered
        # shared-prefix workload must surface router.route events
        # (replica + decision time) and a "fleet serve" report
        # section with per-replica routed counts and a real
        # prefix-route hit
        rlog = os.path.join(d, "router.jsonl")
        from paddle_tpu.inference.router import ServeRouter
        sink = telemetry.attach_jsonl(rlog)
        try:
            bats = [ContinuousBatcher(model, max_batch_size=1,
                                      max_len=32, chunk=4,
                                      prefill_chunk=4, page_size=8)
                    for _ in range(2)]
            router = ServeRouter(batchers=bats)
            shared = rng.randint(1, 64, 12).astype(np.int32)
            tails = [rng.randint(1, 64, t).astype(np.int32)
                     for t in (3, 4, 5, 6)]
            for t in tails[:2]:
                router.submit(np.concatenate([shared, t]), 4)
            for _ in range(8):      # let the shared prefix land
                router.step()
            for t in tails[2:]:
                router.submit(np.concatenate([shared, t]), 4)
            router.run()
        finally:
            telemetry.remove_sink(sink)
        revents = load_events(rlog)
        routes = [e for e in revents
                  if e.get("event") == "router.route"]
        if len(routes) != 4:
            problems.append(f"expected 4 router.route events, got "
                            f"{len(routes)}")
        for e in routes:
            for k in ("req", "replica", "prefix_hit", "decision_ms"):
                if k not in e:
                    problems.append(f"router.route missing {k!r}: {e}")
        rrep = analyze(revents)
        fleet = rrep.get("serve", {}).get("fleet")
        if not fleet:
            problems.append(f"report missing fleet serve section: "
                            f"{rrep}")
        elif not (fleet["routed"] == 4
                  and sum(fleet["routed_by_replica"].values()) == 4
                  and fleet["prefix_route_hit_rate"] > 0
                  and "decision_ms_p50" in fleet):
            problems.append(f"fleet serve section wrong: {fleet}")
        print(render(rrep))

        # disaggregated hand-off leg (ISSUE 20): a prefill+decode
        # split fleet must surface paired serve.handoff export/import
        # events plus router.handoff latency records, and a "handoff"
        # report section whose export/import counts balance
        dlog = os.path.join(d, "disagg.jsonl")
        sink = telemetry.attach_jsonl(dlog)
        try:
            bats = [ContinuousBatcher(model, max_batch_size=1,
                                      max_len=32, chunk=4,
                                      prefill_chunk=4, page_size=8,
                                      role=r)
                    for r in ("prefill", "decode")]
            router = ServeRouter(batchers=bats,
                                 roles=["prefill", "decode"])
            for t in (5, 6, 7):
                router.submit(rng.randint(1, 64, t).astype(np.int32),
                              4)
            router.run()
        finally:
            telemetry.remove_sink(sink)
        devents = load_events(dlog)
        hoffs = [e for e in devents
                 if e.get("event") == "serve.handoff"]
        exps = [e for e in hoffs if e.get("dir") == "export"]
        imps = [e for e in hoffs if e.get("dir") == "import"]
        if not exps or len(exps) != len(imps):
            problems.append(f"unbalanced serve.handoff events: "
                            f"{len(exps)} exports vs "
                            f"{len(imps)} imports")
        for e in hoffs:
            for k in ("dir", "req", "pages", "bytes", "pos"):
                if k not in e:
                    problems.append(f"serve.handoff missing {k!r}: {e}")
        if not any(isinstance(e.get("ms"), (int, float))
                   for e in devents
                   if e.get("event") == "router.handoff"):
            problems.append("no router.handoff latency events")
        drep = analyze(devents)
        hand = drep.get("serve", {}).get("handoff")
        if not hand:
            problems.append(f"report missing handoff section: {drep}")
        elif not (hand["exports"] == len(exps)
                  and hand["imports"] == len(imps)
                  and hand["pages"] > 0 and hand["bytes"] > 0
                  and "ms_p50" in hand):
            problems.append(f"handoff section wrong: {hand}")
        print(render(drep))
    return problems


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="render a telemetry JSONL step log / self-check "
                    "the telemetry plane")
    ap.add_argument("log", nargs="?", help="JSONL step log path")
    ap.add_argument("--selftest", action="store_true",
                    help="run a 5-step toy loop and validate the "
                         "emitted schema; exit 1 on any violation")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    if args.selftest:
        problems = _selftest()
        if problems:
            for p in problems:
                print(f"FAIL {p}")
            return 1
        print("selftest: telemetry schema ok")
        return 0

    if not args.log:
        ap.error("provide a JSONL log path or --selftest")
    rep = analyze(load_events(args.log))
    if args.json:
        print(json.dumps(rep, indent=1))
    else:
        print(render(rep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
