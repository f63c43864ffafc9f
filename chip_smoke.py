#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the repo's two main paths once, through the entry points a user
calls, on ONE TPU chip and in ONE process:

  train   paddle_tpu.parallel.ShardedTrainStep (ZeRO-3 policy, AdamW with
          fp32 parameters and bf16 moments) on llama_7b_config widths —
          hidden 4096, ffn 11008, 32 heads of 128, vocab 32000, seq 2048 —
          cut in DEPTH only, random weights from SEED.  A few steps on one
          repeated batch: every loss finite, the last below the first.
  serve   paddle_tpu.inference.ContinuousBatcher (paged KV, bf16 weights,
          chunked prefill) answers a handful of requests of mixed prompt
          lengths; the tokens are compared with plain generate() on the
          same model.  The paged-attention kernel is then held to its
          XLA twin on one pool (bf16 and int8, decode and chunk widths)
          at this geometry and at the benchmark's serve cell's, where
          both sides' time a call is printed, and a second batcher
          answers two requests from an int8 pool.
  latent  the latent-attention (MLA) kernel is held to ITS XLA twin on one
          pool of 576-wide rows at the latent serve cell's geometry (64
          slots, ragged depths, decode and admission widths), both sides'
          time a call printed.
  moe     the expert layer's dropless path alone
          (incubate...moe.dropless_experts) at the three expert serve
          cells' admission geometry (2,048 tokens, 8 experts a token of a
          router 128 wide), the full-length program against the ladder of
          sorted-buffer lengths at 512, 2,048, 4,096 and 16,384
          assignments held: results compared, a call's device time split
          by scope (moe.dispatch / moe.experts / moe.combine); before it the
          grouped product alone (ops.grouped_matmul, the Pallas kernel,
          beside jax.lax.ragged_dot) at 512, 2,048 and 16,384 rows, ms a
          call and GB/s.  NOT part of the default run:
              python3 -c "import chip_smoke; chip_smoke.phase_moe()"
  lowering  seconds to trace and lower both serve step programs of a
          model built as the window cell's, and the kernel bodies they
          hold (also NOT part of the default run; see phase_lowering).

For both, the compiled program's text must hold the Pallas kernels
(`tpu_custom_call`): flash attention, rms norm, rope and fused AdamW in the
train step, paged attention in the serve step.  The first failure of any
phase ends the run with a non-zero exit code; nothing is caught.

    python3 chip_smoke.py            # one chip, all three phases
    python3 chip_smoke.py --chips 4  # ONLY the four-chip sharded trainer
                                     # and its one-chip comparison

The numbers printed before the last line are SMOKE numbers (one run, a
depth-cut model, compile included where it says so) — not benchmark
results.  The last line of standard output is the contract:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The compile cache is wherever JAX_COMPILATION_CACHE_DIR says, else the
fixed <repo>/.jax_cache (paddle_tpu.telemetry.compile_cache); this script
sets none.
"""
import argparse
import gc
import json
import re
import sys
import time

SEED = 0
SEQ = 2048
BATCH = 2                # the four-chip mesh shards the batch two ways
STEPS = 4
# At 3e-4, with no warm-up on one repeated batch, training overshoots
# by the fourth step at these widths (losses 10.88, 9.14, 7.31, 10.62 —
# my chip run, PR 21).  A smoke wants a loss that simply falls.
LEARNING_RATE = 1e-4
# Depth: compiled.memory_analysis() for a described v5e (rehearsal 3, PR 21)
# puts this train step at 13.09 GiB with 5 layers, 14.75 GiB with 6 and
# (batch 1) 15.01 GiB with 7.  The compiler accepts all three; 5 keeps
# ~2.5 GiB of the chip's 15.75 GiB for what the program does not count.
TRAIN_DEPTH = 5
SERVE_DEPTH = 4
SERVE_SLOTS = 4
SERVE_MAX_LEN = 256
SERVE_PROMPT_LENS = (9, 40, 100, 72, 40, 130)   # 6 requests over 4 slots;
PREFILL_CHUNK = 64                              # 100 and 130 take >1 chunk
NEW_TOKENS = 16
# Served tokens must equal generate()'s.  The one exception: the two
# candidates TIE in the reference logits.  The logits are bf16, and two
# correct attention implementations (Pallas paged kernel vs XLA dense,
# chunked vs whole prefill) round differently, so where the reference's two
# best logits are equal or ONE bf16 step apart (2**-5 for |x| in [4, 8))
# either may win.  Both requests that left generate() on the chip did so on
# an exact tie, margin 0.0 (my chip runs, PR 21).  See bf16_step().
#
# The paged kernel against its twin, same pool, same query: the outputs are
# bf16 weighted means of the V rows, so they may differ by rounding only —
# 2 bf16 steps at the largest output, relative and absolute (measured on
# the chip: exactly 1 step, both pools, both widths — my chip run, PR 21).
# A wrong page, row mask or int8 scale moves an output by a good part of
# its own size.
PAGED_TOL_STEPS = 2
INT8_PROMPT_LENS = (40, 100)
# Four chips against one, the same kernels on both sides (per shard under
# shard_map on four): bf16 compute, two-way tensor-parallel partial sums in
# another order, and AdamW's normalized update, which turns a last-bit
# gradient difference into a full lr-sized step of either sign.  The first
# loss sees only the forward; later ones also the diverging updates.  The
# XLA paths on four chips against the kernels on one came to 2e-5, 1.2e-4,
# 1.6e-4 and 1.3e-3 (my chip run, PR 21); a sharding fault shows as a loss
# that is not finite, does not fall, or is off by O(1).
LOSS_RTOL_FIRST = 1e-3
LOSS_RTOL = 1e-2

TRAIN_KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv", "rms_norm_fwd", "rms_norm_bwd",
                 "add_rms_norm_fwd", "add_rms_norm_bwd", "rope",
                 "fused_adamw")
SERVE_KERNELS = ("paged_attention", "rms_norm_fwd", "rope")


def say(**fields):
    print("smoke " + json.dumps(fields), flush=True)


def fail(msg):
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def kernels_in(hlo_text):
    """Names of the Pallas kernels a compiled program holds: the name=
    each pallas_call carries shows in the op_name of its tpu_custom_call."""
    found = set()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            # .../rope/pallas_call, or jvp(rms_norm_fwd)/pallas_call when
            # the kernel's name is the outermost scope under autodiff
            m = re.search(r'([A-Za-z0-9_]+)\)*/pallas_call"', line)
            found.add(m.group(1) if m else "<unnamed>")
    return sorted(found)


def require_kernels(phase, found, wanted):
    missing = [k for k in wanted if k not in found]
    say(phase=phase, kernels_found=found, kernels_missing=missing)
    if missing:
        fail(f"{phase}: the compiled program has no tpu_custom_call for "
             f"{missing} (found {found})")


def bf16_step(x):
    """Spacing of bfloat16 values at magnitude |x| (8 significant bits)."""
    import numpy as np
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(x), 2.0 ** -126))) - 7)


def device_mem(dev):
    st = dev.memory_stats()
    return int(st["bytes_in_use"]), int(st["peak_bytes_in_use"])


def release(what):
    """Everything the finished phase held must be gone before the next one
    builds its model: the chip has 16 GB."""
    import jax
    from paddle_tpu import telemetry
    telemetry.reset()        # the ledgers' providers close over the trainer
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    say(phase=what, released=True, live_bytes_after=live)
    if live > 256 * 2 ** 20:
        fail(f"{what}: {live / 2**30:.2f} GiB of arrays still live after "
             "the phase was dropped")


# ---------------------------------------------------------------------------
# train

def build_trainer(mesh, depth, tensor_parallel=False):
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import (LlamaForCausalLM, llama_7b_config,
                                         shard_llama_tp)
    from paddle_tpu.parallel import ShardedTrainStep
    cfg = llama_7b_config(num_hidden_layers=depth, dtype="bfloat16",
                          param_dtype="float32",
                          max_position_embeddings=SEQ)
    paddle.seed(SEED)
    model = LlamaForCausalLM(cfg)
    if tensor_parallel:
        shard_llama_tp(model, mesh)
    opt = paddle.optimizer.AdamW(LEARNING_RATE, parameters=model.parameters(),
                                 weight_decay=0.1, moment_dtype="bfloat16")
    step = ShardedTrainStep(model, opt, mesh, sharding_stage=3,
                            rematerialize=False)
    return cfg, model, step


def train_steps(step, cfg, label, wanted_kernels):
    """Compile (text kept for the kernel check), then STEPS timed steps on
    one repeated batch.  Returns the losses."""
    import numpy as np
    import jax
    import paddle_tpu as paddle
    ids = np.random.RandomState(SEED).randint(
        0, cfg.vocab_size, (BATCH, SEQ)).astype(np.int32)
    x = paddle.to_tensor(ids)
    t0 = time.perf_counter()
    text = step.compiled_hlo(x, x)
    compile_s = time.perf_counter() - t0
    require_kernels(label, kernels_in(text), wanted_kernels)
    losses, walls = [], []
    for _ in range(STEPS):
        t0 = time.perf_counter()
        loss = step(x, x)
        jax.block_until_ready(loss.value)
        walls.append(time.perf_counter() - t0)
        losses.append(float(np.asarray(loss.value)))
    say(phase=label, compile_seconds=round(compile_s, 2),
        first_step_seconds_with_program_load=round(walls[0], 3),
        step_seconds=[round(w, 4) for w in walls[1:]],
        tokens_per_step=BATCH * SEQ, losses=losses)
    if not all(np.isfinite(losses)):
        fail(f"{label}: a loss is not finite: {losses}")
    if not losses[-1] < losses[0]:
        fail(f"{label}: the loss did not fall on a repeated batch: {losses}")
    return losses


def phase_train():
    import numpy as np
    import jax
    from paddle_tpu import telemetry
    from paddle_tpu.distributed.topology import build_mesh
    dev = jax.devices()[0]
    cfg, model, step = build_trainer(build_mesh(devices=[dev]), TRAIN_DEPTH)
    n_params = sum(int(np.prod(p.value.shape)) for p in model.parameters())
    say(phase="train", depth=TRAIN_DEPTH, hidden=cfg.hidden_size,
        ffn=cfg.intermediate_size, heads=cfg.num_attention_heads,
        vocab=cfg.vocab_size, seq=SEQ, batch=BATCH, params=n_params)
    train_steps(step, cfg, "train", TRAIN_KERNELS)
    in_use, peak = device_mem(dev)
    say(phase="train", bytes_in_use=in_use, peak_bytes_in_use=peak,
        xla_cache=dict(telemetry.compile_report()["xla_cache"]))


# ---------------------------------------------------------------------------
# serve

def serve_requests(bat, prompts, label):
    """Check the batcher's two step programs for the paged kernel, then
    answer `prompts`.  Returns the served tokens, one array a request."""
    import numpy as np
    t0 = time.perf_counter()
    found = set()
    for mixed in (True, False):
        found.update(kernels_in(bat.lower_step(mixed=mixed).compile()
                                .as_text()))
    compile_s = time.perf_counter() - t0
    require_kernels(label, sorted(found), SERVE_KERNELS)

    first_tok, done_at = {}, {}

    def on_token(rid, tokens, done):
        now = time.perf_counter()
        first_tok.setdefault(rid, now)
        if done:
            done_at[rid] = now

    t0 = time.perf_counter()
    rids = [bat.submit(p, max_new_tokens=NEW_TOKENS, on_token=on_token)
            for p in prompts]
    out = bat.run()               # each chunk ends in a host transfer
    wall = time.perf_counter() - t0
    served = [np.asarray(out[r]) for r in rids]
    st = bat.stats()
    say(phase=label, kv_dtype=st["kv_dtype"],
        compile_seconds=round(compile_s, 2),
        run_seconds_with_program_load=round(wall, 3),
        first_token_seconds=[round(first_tok[r] - t0, 3) for r in rids],
        request_seconds=[round(done_at[r] - t0, 3) for r in rids],
        compiled_programs=st["compiled_programs"],
        admit_chunks=st["admit_chunks"], decode_chunks=st["decode_chunks"],
        prefill_tokens=st["prefill_tokens"],
        decode_tokens=st["decode_tokens"],
        requests_completed=st["requests_completed"],
        requests_shed=st["requests_shed"],
        callback_errors=st["callback_errors"])
    if st["requests_completed"] != len(prompts) or st["requests_shed"] \
            or st["callback_errors"]:
        fail(f"{label}: not every request completed cleanly: {st}")
    vocab = bat.model.config.vocab_size
    for r, toks in zip(rids, served):
        if toks.shape != (NEW_TOKENS,) or toks.min() < 0 \
                or toks.max() >= vocab:
            fail(f"{label}: request {r} returned {toks!r}")
    return served


def require_equal_to_generate(model, prompts, served):
    """Served tokens equal plain generate()'s, one prompt at a time —
    or leave them where the reference logits tie (see the constants)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.inference.generation import generate
    t0 = time.perf_counter()
    ref = [np.asarray(generate(model, p[None], max_new_tokens=NEW_TOKENS)
                      .value)[0] for p in prompts]
    say(phase="serve", generate_seconds_with_compiles=round(
        time.perf_counter() - t0, 2))

    # the judge of ties: teacher-forced logits of every prompt + served
    # answer, one plain forward
    ids = np.zeros((len(prompts), SERVE_MAX_LEN), np.int32)
    for i, (p, toks) in enumerate(zip(prompts, served)):
        ids[i, :len(p)] = p
        ids[i, len(p):len(p) + NEW_TOKENS] = toks
    with paddle.no_grad():
        logits = np.asarray(model(paddle.to_tensor(ids)).value, np.float32)
    exact, ties = 0, []
    for i, (p, toks, rtoks) in enumerate(zip(prompts, served, ref)):
        lg = logits[i, len(p) - 1:len(p) - 1 + NEW_TOKENS]  # row t -> tok t
        top = lg.max(axis=-1)
        gap = top - lg[np.arange(NEW_TOKENS), toks]
        if (gap > bf16_step(top)).any():
            t = int((gap - bf16_step(top)).argmax())
            fail(f"serve: request {i} token {t} ({int(toks[t])}) is "
                 f"{gap[t]:.4f} below the reference maximum {top[t]:.4f}: "
                 f"more than one bf16 step ({bf16_step(top[t])})")
        diff = np.nonzero(toks != rtoks)[0]
        if diff.size == 0:
            exact += 1
            continue
        t = int(diff[0])          # same prefix up to t: one set of logits
        margin = float(abs(lg[t, toks[t]] - lg[t, rtoks[t]]))
        ties.append({"request": i, "token": t, "margin": margin,
                     "bf16_step": float(bf16_step(top[t]))})
        if margin > bf16_step(top[t]):
            fail(f"serve: request {i} leaves generate() at token {t} "
                 f"({int(toks[t])} vs {int(rtoks[t])}) where the reference "
                 f"logits differ by {margin:.4f}: more than one bf16 step "
                 f"({bf16_step(top[t])})")
    say(phase="serve", requests=len(prompts), equal_to_generate=exact,
        left_generate_on_a_tie=ties)


# The benchmark's serve cell (dsllm7b_serve_chat_c24 under the batcher's
# defaults): 24 slots, 66 pages of 16 rows a slot, admission width 32.
CELL_SLOTS, CELL_PAGES_PER_SLOT, CELL_CHUNK = 24, 66, 32
TIMED_CALLS = 20
# the latent (MLA) serve cell's geometry, sarvam105b_serve_chat_c64: 64
# slots x 256 pages of 16 rows, 64 heads on rows of 512 + 64, one layer of
# the pool (0.3 GB)
LATENT_SLOTS, LATENT_PAGES_PER_SLOT, LATENT_CHUNK = 64, 256, 32
LATENT_HEADS, LATENT_RANK, LATENT_ROPE, LATENT_SCALE = 64, 512, 64, 0.135
# the expert layer at the three expert serve cells' admission step: (hidden,
# an expert's width, experts HELD of the router's MOE_ROUTER), and how many
# of a step's MOE_TOKENS * MOE_TOP_K assignments are held by a valid lane
MOE_GEOMETRIES = {"k-exaone-236b-a23b": (6144, 2048, 16),
                  "sarvam-105b": (4096, 2048, 32),
                  "sdar-30b-a3b-chat": (2048, 768, 128)}
MOE_TOKENS, MOE_TOP_K, MOE_ROUTER = 2048, 8, 128
MOE_HELD = (512, 2048, 4096, 16384)
MOE_TRACED_CALLS = 5
# the grouped product alone (ops.grouped_matmul against jax.lax.ragged_dot):
# rows of the sorted buffer and the rows held among them — a decode step of
# the sigmoid cells (64 slots x 8: about 64 held), sdar30b_serve_gen_c64's
# decode pass (1,920 of 2,048) and an admission step's whole buffer
GROUPED_ROWS_HELD = ((512, 64), (2048, 1920), (16384, 16384))


def ms_a_call(fn, args):
    """Wall time of one call in ms, over TIMED_CALLS calls dispatched one
    behind the other after a warm one: the device's time a call where
    that is longer than the host's dispatch (about 0.2 ms), an upper
    bound on it either way."""
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(TIMED_CALLS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / TIMED_CALLS * 1e3


def twin_gap(got, want):
    """(fields to print, within tolerance?) of a kernel's output against
    its twin's: PAGED_TOL_STEPS bf16 steps at the largest output,
    relative and absolute."""
    import numpy as np
    tol = PAGED_TOL_STEPS * float(bf16_step(np.abs(want).max()))
    fields = dict(max_abs_difference=float(np.abs(got - want).max()),
                  largest_output=float(np.abs(want).max()), tolerance=tol)
    return fields, bool(np.isfinite(got).all() and np.allclose(
        got, want, rtol=PAGED_TOL_STEPS * 2.0 ** -8, atol=tol))


def require_paged_kernel_equals_twin(cfg, page_size):
    """The Pallas paged-attention kernel against ops.xla_paged_attention
    on the same pool and query: a bf16 and an int8 pool filled by the
    repo's own writer (ops.paged_kv_update, which quantizes), scattered
    pages, decode (C = 1) and chunk widths.  Two geometries: the serve
    phase's, depths on both sides of page boundaries, and the
    benchmark's serve cell's, 12 to 60 live pages of 66 a slot as its
    traffic leaves them — there each line also carries both sides' time
    a call (the smoke's own calls are shorter than their dispatch)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu import ops
    from paddle_tpu.ops.pallas import paged_attention as kernel
    n_kv, heads, hd = (cfg.num_key_value_heads, cfg.num_attention_heads,
                       cfg.attn_head_dim)
    rng = np.random.RandomState(SEED + 2)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    cell_depths = page_size * rng.randint(12, 61, CELL_SLOTS) \
        - rng.randint(1, page_size + 1, CELL_SLOTS)
    geometries = (
        ("smoke", SERVE_SLOTS, SERVE_MAX_LEN // page_size, PREFILL_CHUNK,
         ((1, (15, 16, 17, SERVE_MAX_LEN - 1)),
          (PREFILL_CHUNK, (0, 16, 100, SERVE_MAX_LEN - PREFILL_CHUNK)))),
        ("cell", CELL_SLOTS, CELL_PAGES_PER_SLOT, CELL_CHUNK,
         ((1, cell_depths), (CELL_CHUNK, cell_depths - CELL_CHUNK))))
    write = jax.jit(ops.paged_kv_update, static_argnums=(8,))
    fn = jax.jit(ops.paged_attention, static_argnums=(5,))
    twin = jax.jit(ops.xla_paged_attention, static_argnums=(5,))
    for name, slots, per_slot, chunk, cases in geometries:
        n_pages = 1 + slots * per_slot             # page 0: the null page
        n_rows = per_slot * page_size // chunk * chunk
        table = jnp.asarray(1 + rng.permutation(n_pages - 1).reshape(
            slots, per_slot), jnp.int32)
        k_rows = normal(slots, n_rows, n_kv, hd)
        v_rows = normal(slots, n_rows, n_kv, hd)
        for quant in (False, True):
            shape = (n_pages, 1, n_kv, page_size, hd)
            if not kernel.supports(shape):
                fail(f"serve: paged_attention.supports refuses the pool "
                     f"{shape}")
            pool = [jnp.zeros(shape, jnp.int8 if quant else jnp.bfloat16)] * 2
            scales = [jnp.ones(shape[:3], jnp.float32)] * 2 if quant \
                else [None, None]
            for r0 in range(0, n_rows, chunk):
                rows = slice(r0, r0 + chunk)
                *pool, ks, vs = write(
                    *pool, *scales, table,
                    jnp.full((slots,), r0, jnp.int32),
                    k_rows[:, rows], v_rows[:, rows], 0)
                scales = [ks, vs]
            for width, depths in cases:
                q = normal(slots, width, heads, hd)
                pos = jnp.asarray(depths, jnp.int32)
                args = (q, *pool, table, pos, 0, *scales)
                if "paged_attention" not in kernels_in(
                        fn.lower(*args).compile().as_text()):
                    fail("serve: ops.paged_attention compiled without its "
                         "kernel")
                got = np.asarray(fn(*args), np.float32)
                want = np.asarray(twin(*args), np.float32)
                gap, close = twin_gap(got, want)
                times = dict(kernel_ms_a_call=ms_a_call(fn, args),
                             twin_ms_a_call=ms_a_call(twin, args)) \
                    if name == "cell" else {}
                say(phase="serve", paged_kernel_vs_twin=dict(
                    geometry=name, pool="int8" if quant else "bf16",
                    width=width, live_pages=int(np.sum(kernel.pages_walked(
                        np.asarray(depths), width, page_size, per_slot))),
                    **gap, **times))
                if not close:
                    fail(f"serve: the paged kernel leaves its twin "
                         f"({name} geometry, pool int8={quant}, width "
                         f"{width}): {gap}")


def phase_latent(page_size=16):
    """The latent-attention kernel (ops/pallas/latent_attention.py)
    against ops.xla_latent_paged_attention on one bf16 pool filled by the
    repo's own writer (ops.latent_kv_update), scattered pages, at the
    latent serve cell's geometry: decode (C = 1) and admission (C = 32)
    widths; depths ragged as that cell's traffic leaves them (lognormal,
    median 768, 64 to 3.5 k rows), among them a slot at depth 0, depths on
    both sides of a page's and of a block's end, the table's last row, and
    a free slot whose table is all null pages.  Each line carries both
    sides' time a call (either includes 1.0 ms in which XLA copies the
    pool, an argument here, out of the pages-minor layout it gives a
    576-wide array: a step program pays that once a chunk)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu import ops
    from paddle_tpu.ops.pallas import latent_attention as kernel
    from paddle_tpu.ops.pallas.paged_attention import pages_walked
    slots, per_slot, width = LATENT_SLOTS, LATENT_PAGES_PER_SLOT, \
        LATENT_RANK + LATENT_ROPE
    rng = np.random.RandomState(SEED + 3)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    n_pages = 1 + slots * per_slot                 # page 0: the null page
    n_rows = per_slot * page_size
    shape = (n_pages, 1, page_size, width)
    if not kernel.supports(shape, LATENT_RANK, jnp.bfloat16):
        fail(f"latent: latent_attention.supports refuses the pool {shape}")
    table = 1 + rng.permutation(n_pages - 1).reshape(slots, per_slot)
    table[1] = 0                                   # a free slot
    table = jnp.asarray(table, jnp.int32)
    write = jax.jit(ops.latent_kv_update, static_argnums=(4,),
                    donate_argnums=(0,))
    pool = jnp.zeros(shape, jnp.bfloat16)
    for r0 in range(0, n_rows, 512):
        pool = write(pool, table, jnp.full((slots,), r0, jnp.int32),
                     normal(slots, 512, width), 0)
    depths = np.clip(np.exp(rng.normal(np.log(768.0), 0.8, slots)),
                     64, 3500).astype(np.int64)
    depths[:8] = (0, 0, 15, 16, 255, 256, 257, n_rows - LATENT_CHUNK)
    fn = jax.jit(ops.latent_paged_attention, static_argnums=(5, 6))
    twin = jax.jit(ops.xla_latent_paged_attention, static_argnums=(5, 6))
    for lanes in (1, LATENT_CHUNK):
        q_lat = normal(slots, lanes, LATENT_HEADS, LATENT_RANK)
        q_rope = normal(slots, lanes, LATENT_HEADS, LATENT_ROPE)
        args = (q_lat, q_rope, pool, table, jnp.asarray(depths, jnp.int32),
                0, LATENT_SCALE)
        if "latent_attention" not in kernels_in(
                fn.lower(*args).compile().as_text()):
            fail("latent: ops.latent_paged_attention compiled without its "
                 "kernel")
        got = np.asarray(fn(*args), np.float32)
        want = np.asarray(twin(*args), np.float32)
        gap, close = twin_gap(got, want)
        say(phase="latent", latent_kernel_vs_twin=dict(
            width=lanes, live_pages=int(np.sum(pages_walked(
                depths, lanes, page_size, per_slot))),
            twin_pages=int(np.sum(ops.latent_pages_walked(
                depths, lanes, page_size, per_slot))),
            **gap, kernel_ms_a_call=ms_a_call(fn, args),
            twin_ms_a_call=ms_a_call(twin, args)))
        if not close:
            fail(f"latent: the latent kernel leaves its twin (width "
                 f"{lanes}): {gap}")


def moe_routing(rng, count, n_held):
    """topi [MOE_TOKENS, MOE_TOP_K] (distinct experts a token) and valid
    [MOE_TOKENS] with exactly n_held assignments held by a valid lane: a
    layer that holds a share gives each valid token as few held choices as
    reach n_held (1 where n_held tokens exist), one that holds every expert
    of the router makes n_held / k tokens valid."""
    import numpy as np
    S, k, width = MOE_TOKENS, MOE_TOP_K, MOE_ROUTER
    n_tok, per = (n_held // k, k) if count == width \
        else (min(S, n_held), n_held // min(S, n_held))
    topi = np.empty((S, k), np.int32)
    for s in range(S):
        held = rng.permutation(count)[:per]
        absent = count + rng.permutation(width - count)[:k - per]
        topi[s] = rng.permutation(np.concatenate([held, absent]))
    return topi, np.arange(S) < n_tok


def scope_ms_a_call(fn, args, calls):
    """Device time of one call in ms by the expert layer's scopes, from a
    profiler trace of `calls` calls (under the benchmark's .bench_trace):
    the leaf operations' `op_name`s as benchmark/program_spans.py reads
    them (the grouped product's kernels carry none and are named
    `ragged-dot-*`: with moe.experts)."""
    import os
    import jax
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "benchmark"))
    try:
        import harness
        import program_spans
    finally:
        sys.path.pop(0)
    jax.block_until_ready(fn(*args))
    tracer = harness.Tracer("chip_smoke_moe", True)
    tracer.start()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    tracer.stop()
    split = dict.fromkeys(("moe.dispatch", "moe.experts", "moe.combine",
                           "other"), 0.0)
    for name, dur in program_spans.load(tracer.xplane_path()).device_leaves:
        scope = "moe.experts" if "ragged-dot" in name else next(
            (s for s in split if s in name), "other")
        split[scope] += dur * 1e-6 / calls
    return {k: round(v, 3) for k, v in split.items()}


def grouped_sizes(rng, count, held):
    """`held` rows over `count` experts, skewed as a router leaves them
    (some experts empty, a few large): int32 [count]."""
    import numpy as np
    p = 1.0 / np.arange(1, count + 1) ** 1.2
    return np.bincount(rng.choice(count, held, p=p / p.sum()),
                       minlength=count).astype(np.int32)


def phase_grouped():
    """ops.grouped_matmul (the Pallas kernel on a TPU) beside
    jax.lax.ragged_dot, both products of the three expert cells' layers at
    their real widths, at GROUPED_ROWS_HELD: the kernel must be in the
    compiled program and equal ragged_dot on the held rows; each line
    carries both sides' ms a call and GB/s (the hit experts' weights once,
    the held rows in and out)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu import ops
    if jax.devices()[0].platform != "tpu":
        fail("grouped: the kernel runs on a TPU only")
    rng = np.random.RandomState(SEED + 5)
    key = jax.random.PRNGKey(SEED + 5)
    kernel = jax.jit(ops.grouped_matmul)
    twin = jax.jit(jax.lax.ragged_dot)
    for name, (d, f, count) in MOE_GEOMETRIES.items():
        for product, (K, N) in (("gate_up", (d, 2 * f)), ("down", (f, d))):
            w = jax.random.normal(key, (count, K, N), jnp.bfloat16) * K ** -0.5
            for R, held in GROUPED_ROWS_HELD:
                sizes = grouped_sizes(rng, count, held)
                rows = jax.random.normal(key, (R, K), jnp.bfloat16)
                args = (rows, w, jnp.asarray(sizes))
                if "grouped_matmul" not in kernels_in(
                        kernel.lower(*args).compile().as_text()):
                    fail("grouped: ops.grouped_matmul compiled without its "
                         "kernel")
                got = np.asarray(kernel(*args), np.float32)[:held]
                want = np.asarray(twin(*args), np.float32)[:held]
                gap, close = twin_gap(got, want)
                nbytes = 2 * (int((sizes > 0).sum()) * K * N + held * (K + N))
                times = {side: ms_a_call(fn, args)
                         for side, fn in (("kernel", kernel), ("twin", twin))}
                say(phase="grouped", config=name, product=product, rows=R,
                    held=held, hit=int((sizes > 0).sum()), K=K, N=N, **gap,
                    kernel_ms_a_call=round(times["kernel"], 4),
                    twin_ms_a_call=round(times["twin"], 4),
                    kernel_gb_s=round(nbytes / times["kernel"] / 1e6, 1),
                    twin_gb_s=round(nbytes / times["twin"] / 1e6, 1))
                if not close:
                    fail(f"grouped: the kernel leaves ragged_dot ({name} "
                         f"{product}, {R} rows): {gap}")
                del rows, args
            del w
        release("grouped")


# the hybrid serve cell's state pool: 36 state layers, 64 slots, 64 heads of
# 64, a state of 128 (graniteh3b_serve_chat_c64)
SSM_GEOMETRY = (36, 64, 64, 64, 128)


def phase_ssm():
    """ops.ssm_scan (the Pallas kernel on a TPU) beside ops.xla_ssm_scan at
    the hybrid serve cell's geometry, a decode call (1 lane) and an
    admission call (32 lanes, 20 valid a slot), the pool donated and passed
    back call after call as the step program does: the kernel must be in
    the compiled program and equal the twin; each line carries both sides'
    ms a call and GB/s of the layer's state read and written."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu import ops
    if jax.devices()[0].platform != "tpu":
        fail("ssm: the kernel runs on a TPU only")
    L, B, H, P, N = SSM_GEOMETRY
    key = jax.random.PRNGKey(SEED + 6)
    a = -jnp.exp(jnp.linspace(0.0, 2.7, H, dtype=jnp.float32))
    d = jnp.ones((H,), jnp.float32)
    reset = jnp.zeros((B,), jnp.int32)
    state_bytes = 2 * B * H * P * N * 4
    for width, valid in ((1, 1), (32, 20)):
        ks = jax.random.split(key, 4)
        x = jax.random.normal(ks[0], (B, width, H, P), jnp.bfloat16)
        bm = (jax.random.normal(ks[1], (B, width, N)) * 0.1).astype(
            jnp.bfloat16)
        cm = (jax.random.normal(ks[2], (B, width, N)) * 0.1).astype(
            jnp.bfloat16)
        dt = jax.random.uniform(ks[3], (B, width, H), jnp.float32, 0.001,
                                0.1)
        n = jnp.full((B,), valid, jnp.int32)
        out, times = {}, {}
        for side, fn in (("kernel", ops.ssm_scan), ("twin", None)):
            def call(state, x, bm, cm, dt, n, layer, fn=fn):
                if fn is not None:
                    return fn(state, x, bm, cm, dt, a, d, n, reset, layer)
                lanes = jnp.arange(width)
                m = jnp.where(lanes[None, :, None] < n[:, None, None], dt,
                              0.0)
                return ops.xla_ssm_scan(state, x, bm, cm, m, a, d, reset,
                                        layer)
            step = jax.jit(call, donate_argnums=(0,))
            state = jax.random.normal(key, (L, B, H, P, N), jnp.float32)
            if side == "kernel" and "ssm_scan" not in kernels_in(
                    step.lower(state, x, bm, cm, dt, n, jnp.int32(3))
                    .compile().as_text()):
                fail("ssm: ops.ssm_scan compiled without its kernel")
            y, state = step(state, x, bm, cm, dt, n, jnp.int32(3))
            out[side] = (np.asarray(y[:, :valid], np.float32),
                         np.asarray(state[3, :4], np.float32))
            jax.block_until_ready(state)
            t0 = time.perf_counter()
            for i in range(TIMED_CALLS):
                y, state = step(state, x, bm, cm, dt, n, jnp.int32(i % L))
            jax.block_until_ready(state)
            times[side] = (time.perf_counter() - t0) / TIMED_CALLS * 1e3
            del state, y
        gap, close = twin_gap(out["kernel"][0], out["twin"][0])
        state_gap = float(np.abs(out["kernel"][1] - out["twin"][1]).max())
        say(phase="ssm", width=width, valid=valid, **gap,
            state_max_abs_difference=state_gap,
            kernel_ms_a_call=round(times["kernel"], 4),
            twin_ms_a_call=round(times["twin"], 4),
            kernel_state_gb_s=round(state_bytes / times["kernel"] / 1e6, 1),
            twin_state_gb_s=round(state_bytes / times["twin"] / 1e6, 1))
        if not close:
            fail(f"ssm: the kernel leaves the twin ({width} lanes): {gap}")
    release("ssm")


def phase_lowering():
    """Seconds this host takes to trace and lower the two serve step
    programs of a model built as kexaone236b_serve_mixed_c64's (5 layers of
    kinds L L L G L, layer 0 dense, 16 of 128 experts held, 8 a token, a
    shared expert; 64 slots of 32 admission lanes: a ladder of three rungs
    in the admission program, one in the decode program) at small widths,
    and the `grouped_matmul` kernel bodies each lowered program holds.  It
    imports whatever `paddle_tpu` the path gives, so the same function
    times a checkout without the kernel:
        cd <other checkout> && python3 -c "import runpy; runpy.run_path(
            '<this file>', run_name='lowering')"
    """
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatcher
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(SEED)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=1024, hidden_size=512, intermediate_size=1024,
        num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
        head_dim=128, use_qk_norm=True, max_position_embeddings=1024,
        rope_theta=1e6, dtype="bfloat16",
        layer_types=("sliding_attention",) * 3
        + ("full_attention", "sliding_attention"), sliding_window=128,
        rope_layer_types=("sliding_attention",), first_k_dense_replace=1,
        moe_gate="sigmoid", moe_num_experts=16, moe_first_expert=0,
        moe_router_width=128, moe_top_k=8, moe_intermediate_size=256,
        moe_shared_experts=1, moe_routed_scaling=2.5, moe_router_bias=True))
    model.eval()
    bat = ContinuousBatcher(model, max_batch_size=64, max_len=1024)
    line = dict(paddle_tpu=paddle.__file__, slots=64,
                prefill_chunk=bat.prefill_chunk, backend=jax.default_backend())
    for kind, mixed in (("admit", True), ("decode", False)):
        t0 = time.perf_counter()
        text = bat.lower_step(mixed=mixed).as_text()
        line[kind + "_trace_lower_s"] = round(time.perf_counter() - t0, 3)
        line[kind + "_grouped_matmul_bodies"] = text.count(
            'kernel_name = "grouped_matmul"')
    say(phase="lowering", **line)


def phase_moe():
    """The grouped product alone (`phase_grouped`); then `dropless_experts`
    alone, the full-length program (the only rung all S*k: what every call
    ran before the ladder) against the ladder, at the three expert cells'
    admission geometry: the table that sets the rungs."""
    phase_grouped()
    import functools
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.incubate.distributed.models import moe
    S, k = MOE_TOKENS, MOE_TOP_K
    if jax.devices()[0].platform != "tpu":
        fail("moe: a device time comes from a TPU's trace only")
    rng = np.random.RandomState(SEED + 4)
    key = jax.random.PRNGKey(SEED + 4)

    def experts(lengths, x, topi, topw, valid, w1, w2):
        return moe._sorted_experts(x, topi, topw, w1, w2, "swiglu", 0, valid,
                                   None, None, None, lengths)
    programs = {"full": jax.jit(functools.partial(experts, (S * k,))),
                "ladder": jax.jit(functools.partial(
                    experts, moe.sorted_lengths(S * k)))}
    say(phase="moe", tokens=S, top_k=k, router=MOE_ROUTER,
        lengths=moe.sorted_lengths(S * k))
    for name, (d, f, count) in MOE_GEOMETRIES.items():
        w1 = jax.random.normal(key, (count, d, 2 * f), jnp.bfloat16) * 0.02
        w2 = jax.random.normal(key, (count, f, d), jnp.bfloat16) * 0.02
        x = jax.random.normal(key, (S, d), jnp.bfloat16)
        topw = jax.random.uniform(key, (S, k), jnp.float32)
        for n_held in MOE_HELD:
            topi, valid = moe_routing(rng, count, n_held)
            args = (x, jnp.asarray(topi), topw, jnp.asarray(valid), w1, w2)
            got = {p: np.asarray(fn(*args)) for p, fn in programs.items()}
            gap, close = twin_gap(got["ladder"], got["full"])
            line = dict(config=name, hidden=d, expert_width=f, held=count,
                        assignments_held=n_held, **gap)
            for p, fn in programs.items():
                line[p + "_ms_a_call"] = round(ms_a_call(fn, args), 3)
                line[p + "_scope_ms"] = scope_ms_a_call(
                    fn, args, MOE_TRACED_CALLS)
            say(phase="moe", **line)
            if not close:
                fail(f"moe: the ladder leaves the full-length program "
                     f"({name}, {n_held} held): {gap}")
        del w1, w2, x, args, got
        release("moe")


def phase_serve():
    import numpy as np
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatcher
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_7b_config
    dev = jax.devices()[0]
    cfg = llama_7b_config(num_hidden_layers=SERVE_DEPTH, dtype="bfloat16",
                          max_position_embeddings=SEQ)
    paddle.seed(SEED)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(SEED + 1)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in SERVE_PROMPT_LENS]
    geometry = dict(max_batch_size=SERVE_SLOTS, max_len=SERVE_MAX_LEN,
                    chunk=16, prefill_chunk=PREFILL_CHUNK)
    bat = ContinuousBatcher(model, **geometry)
    say(phase="serve", depth=SERVE_DEPTH, hidden=cfg.hidden_size,
        kv_layout=bat.kv_layout, page_size=bat.page_size,
        num_pages=bat.num_pages, slots=SERVE_SLOTS,
        prompt_lens=list(SERVE_PROMPT_LENS), new_tokens=NEW_TOKENS)
    if bat.kv_layout != "paged":
        fail("serve: the batcher's default kv_layout is not 'paged'")
    served = serve_requests(bat, prompts, "serve")
    require_equal_to_generate(model, prompts, served)
    require_paged_kernel_equals_twin(cfg, bat.page_size)

    # an int8 pool is lossy by design (tests/test_paged_kv.py: "greedy
    # flips are legal under quantization"), so its tokens are reported
    # against the bf16 pool's, not required to equal them; the kernel's
    # int8 arithmetic is what the twin comparison above holds exact
    by_len = dict(zip(SERVE_PROMPT_LENS, zip(prompts, served)))
    bat8 = ContinuousBatcher(model, kv_dtype="int8", **geometry)
    served8 = serve_requests(bat8, [by_len[n][0] for n in INT8_PROMPT_LENS],
                             "serve_int8")
    if bat8.stats()["kv_dtype"] != "int8":
        fail(f"serve_int8: the pool is {bat8.stats()['kv_dtype']}")
    say(phase="serve_int8", prompt_lens=list(INT8_PROMPT_LENS),
        tokens_equal_to_bf16_pool=[
            int((a == by_len[n][1]).sum())
            for n, a in zip(INT8_PROMPT_LENS, served8)],
        of=NEW_TOKENS)
    in_use, peak = device_mem(dev)
    say(phase="serve", bytes_in_use=in_use, peak_bytes_in_use=peak)


# ---------------------------------------------------------------------------
# four chips (--chips 4): the sharded trainer and its one-chip comparison

def require_four_way_shards(model, devs):
    """Every parameter on all four chips, every matrix a quarter per chip
    (1-D leaves are replicated by the trainer's policy)."""
    for name, p in model.named_parameters():
        arr = p.value
        shards = arr.addressable_shards
        on = {s.device for s in shards}
        if on != set(devs):
            fail(f"train4: {name} lives on {sorted(d.id for d in on)}, "
                 "not on all four chips")
        if arr.ndim >= 2 and any(s.data.size * 4 != arr.size
                                 for s in shards):
            fail(f"train4: {name} {arr.shape} is not split four ways: "
                 f"shards {[s.data.shape for s in shards]}")


def phase_four_chips():
    import jax
    from paddle_tpu.distributed.topology import build_mesh
    devs = jax.devices()
    if len(devs) != 4:
        fail(f"--chips 4 needs four local chips, jax reports {len(devs)}")
    mesh = build_mesh(sharding=2, mp=2, devices=devs)
    say(phase="train4", mesh={a: int(n) for a, n in mesh.shape.items()},
        mesh_device_ids=[int(d.id) for d in mesh.devices.flat],
        depth=TRAIN_DEPTH, seq=SEQ, batch=BATCH)
    cfg, model, step = build_trainer(mesh, TRAIN_DEPTH, tensor_parallel=True)
    # the same kernels as on one chip, each per shard under shard_map
    losses4 = train_steps(step, cfg, "train4", TRAIN_KERNELS)
    require_four_way_shards(model, devs)
    mem = [device_mem(d) for d in devs]
    say(phase="train4", bytes_in_use=[m[0] for m in mem],
        peak_bytes_in_use=[m[1] for m in mem])
    # what stays resident is the sharded state: near-equal quarters.  The
    # high-water mark also holds the first chip's unsharded init, so it
    # only has to be of one order (a fault puts 4x, or all, on one chip)
    for what, vals, ratio in (("bytes_in_use", [m[0] for m in mem], 1.5),
                              ("peak_bytes_in_use", [m[1] for m in mem], 3)):
        if max(vals) > ratio * min(vals):
            fail(f"train4: {what} is not of one order across the chips: "
                 f"{vals}")
    del model, step
    release("train4")

    cfg, model, step = build_trainer(build_mesh(devices=devs[:1]),
                                     TRAIN_DEPTH)
    losses1 = train_steps(step, cfg, "train1", TRAIN_KERNELS)
    rel = [abs(a - b) / abs(b) for a, b in zip(losses4, losses1)]
    say(phase="train4", losses_four_chips=losses4, losses_one_chip=losses1,
        relative_difference=[round(r, 5) for r in rel],
        tolerance=[LOSS_RTOL_FIRST] + [LOSS_RTOL] * (STEPS - 1))
    if rel[0] > LOSS_RTOL_FIRST or max(rel) > LOSS_RTOL:
        fail(f"train4: losses leave the one-chip run: {rel}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the four-chip sharded trainer and "
                         "its one-chip comparison")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no accelerator: jax.devices()[0].platform is "
             f"{dev.platform!r}, this script runs on a TPU only")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    from paddle_tpu import telemetry
    say(device=device, jax=jax.__version__,
        compile_cache_dir=telemetry.cache_dir(),
        bytes_limit=dev.memory_stats().get("bytes_limit"))

    if args.chips == 4:
        phase_four_chips()
    else:
        phase_train()
        release("train")
        phase_serve()
        phase_latent()
    say(xla_cache=telemetry.compile_report()["xla_cache"])
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
elif __name__ == "lowering":
    phase_lowering()
