"""The main path's Pallas kernels compile for the real chip, at the widths
`chip_smoke.py` runs (llama_7b_config: hidden 4096, ffn 11008, 32 heads of
128; seq 2048; page_size 16).

The TPU's compiler is installed here and compiles for a chip that is
DESCRIBED, not attached (`v5e:2x2`): what Mosaic or XLA would refuse on the
chip — a block that does not tile, too much VMEM — it refuses here, at no
chip time.  Interpret-mode tests cannot see any of that.  Nothing runs, so
these tests say nothing about results or speed.

Only one process may load the TPU's library, so the topology is described
inside a module-scoped fixture (never at import), every compile happens in
this process, and all of these tests live in this ONE file.
"""
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HIDDEN, FFN, HEADS, HEAD_DIM, SEQ = 4096, 11008, 32, 128, 2048
PAGE_SIZE, PAGES, SLOTS, PAGES_PER_SLOT, LAYERS, CHUNK = 16, 256, 8, 20, 4, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def for_chip(one_chip, monkeypatch):
    """compile(fn, *shapes, donate=()) -> optimized HLO text of `fn`
    compiled for one described v5e chip (a shape is (dims, dtype), or None
    for an argument the call leaves out; `donate` as jit's donate_argnums).
    The kernels pick interpret mode from the backend being the CPU; for
    the length of one test they are told it is a TPU.
    The persistent compilation cache is off around the compile: an entry
    written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def compile_(fn, *shapes, donate=()):
        args = [s and jax.ShapeDtypeStruct(*s, sharding=one_chip)
                for s in shapes]
        return jax.jit(fn, donate_argnums=donate).lower(*args) \
            .compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _kernels(text):
    """The names chip_smoke.py finds in a compiled program — its own
    parser, so the smoke's kernel check is held to real compiler output."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.pop(0)
    return set(chip_smoke.kernels_in(text))


def _sum32(*outs):
    return sum(jnp.sum(o.astype(jnp.float32)) for o in outs)


@pytest.mark.parametrize("kv_heads", [32, 8], ids=["mha", "gqa"])
def test_flash_attention_fwd_bwd(for_chip, kv_heads):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    def f(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: _sum32(flash_attention(q, k, v, causal=True)),
            argnums=(0, 1, 2))(q, k, v)
    bf = jnp.bfloat16
    text = for_chip(f, ((1, SEQ, HEADS, HEAD_DIM), bf),
                    ((1, SEQ, kv_heads, HEAD_DIM), bf),
                    ((1, SEQ, kv_heads, HEAD_DIM), bf))
    assert {"flash_attention_fwd", "flash_attention_bwd_dq",
            "flash_attention_bwd_dkv"} <= _kernels(text)


def test_rms_norm_fwd_bwd(for_chip):
    from paddle_tpu.ops.pallas.rms_norm import rms_norm

    def f(x, w):
        return jax.value_and_grad(
            lambda x, w: _sum32(rms_norm(x, w)), argnums=(0, 1))(x, w)
    text = for_chip(f, ((2, SEQ, HIDDEN), jnp.bfloat16),
                    ((HIDDEN,), jnp.bfloat16))
    assert {"rms_norm_fwd", "rms_norm_bwd"} <= _kernels(text)


def test_add_rms_norm_fwd_bwd(for_chip):
    from paddle_tpu.ops.pallas.rms_norm import fused_add_rms_norm

    def f(x, y, w):
        return jax.value_and_grad(
            lambda x, y, w: _sum32(*fused_add_rms_norm(x, y, w)),
            argnums=(0, 1, 2))(x, y, w)
    bf = jnp.bfloat16
    text = for_chip(f, ((2, SEQ, HIDDEN), bf), ((2, SEQ, HIDDEN), bf),
                    ((HIDDEN,), bf))
    assert {"add_rms_norm_fwd", "add_rms_norm_bwd"} <= _kernels(text)


def test_rope_fwd_bwd(for_chip):
    from paddle_tpu.ops.pallas.rope import rope_apply

    def f(q, k, cos, sin):
        return jax.value_and_grad(
            lambda q, k: _sum32(*rope_apply(q, k, cos, sin)),
            argnums=(0, 1))(q, k)
    bf = jnp.bfloat16
    text = for_chip(f, ((2, SEQ, HEADS, HEAD_DIM), bf),
                    ((2, SEQ, HEADS, HEAD_DIM), bf),
                    ((SEQ, HEAD_DIM), jnp.float32),
                    ((SEQ, HEAD_DIM), jnp.float32))
    assert "rope" in _kernels(text)


def test_kernels_per_shard_on_four_chips(topo, for_chip):
    """What a four-chip trainer traces (chip_smoke --chips 4: sharding 2
    x mp 2): inside ops.kernel_mesh_scope the dispatch runs rope, flash
    attention and both norms under shard_map, and the program compiled
    for the 2x2 mesh holds every kernel, forward and backward."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu import ops
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("sharding", "mp"))

    def loss(x, y, w, q, k, v, cos, sin):
        with ops.kernel_mesh_scope(mesh):
            q, k = ops.apply_rope(q, k, cos, sin)
            a = ops.attention(q, k, v, causal=True)
            resid, h = ops.fused_add_rms_norm(x, y, w)
            return _sum32(a, resid, ops.rms_norm(h, w))
    bf = jnp.bfloat16
    acts, heads, whole = (P("sharding", None, None),
                          P("sharding", None, "mp", None), P())
    shapes = ([((2, SEQ, HIDDEN), bf, acts)] * 2
              + [((HIDDEN,), jnp.float32, whole)]
              + [((2, SEQ, HEADS, HEAD_DIM), bf, heads)] * 3
              + [((SEQ, HEAD_DIM), jnp.float32, whole)] * 2)
    args = [jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh, sp))
            for s, d, sp in shapes]
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4, 5))) \
        .lower(*args).compile().as_text()
    assert {"flash_attention_fwd", "flash_attention_bwd_dq",
            "flash_attention_bwd_dkv", "rope", "rms_norm_fwd",
            "rms_norm_bwd", "add_rms_norm_fwd",
            "add_rms_norm_bwd"} <= _kernels(text)


# the leaves of `mistral7b_train_2k` (gate / up, down, q / o, k / v,
# embedding and head, norms), the shards ZeRO (rows) and `mp` (columns)
# make of the MLP's, an expert stack, and a long one-dimensional operand
# (what `multi_tensor_adamw` concatenates)
ADAMW_LEAVES = [(4096, 14336), (14336, 4096), (4096, 4096), (4096, 1024),
                (32768, 4096), (4096,), (2048, 14336), (4096, 7168),
                (4, 4096, 2048), (40 * 8192,)]


def _adamw_text(for_chip, leaf, master, ef=False):
    """Optimized HLO of one leaf's update with its state donated: bf16
    moments, fp32 parameter (the param IS the master, what the cell
    trains with) or bf16 parameter + fp32 master."""
    from paddle_tpu.ops.pallas.fused_adamw import fused_adamw
    bf, f32 = jnp.bfloat16, jnp.float32

    def f(g, m, v, mst, lr, step, e=None):
        out = fused_adamw(g, m, v, mst, lr, step, wd=0.1, ef=e,
                          out_dtype=bf if master else f32)
        # an fp32 param IS the master: the step keeps one of the two.
        # A new bf16 param goes last: jit pairs donated arguments with
        # results of their type in order, and it has the moments' type
        return out[1:] + out[:1] if master else out[:3] + out[4:]
    return for_chip(f, (leaf, bf if master else f32), (leaf, bf),
                    (leaf, bf), (leaf, f32), ((), f32), ((), jnp.int32),
                    *([(leaf, bf)] if ef else []),
                    donate=(1, 2, 3, 6) if ef else (1, 2, 3))


# %name = type[dims]{layout} op(%first_operand, of an array-valued instruction
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+\[([\d,]+)\]\{[^ ]*\}) "
                    r"([\w\-]+)\(%([\w.\-]+)")


def _leaf_sized(text, n):
    """The instructions of an optimized program that RELAY a whole leaf:
    a `reshape` or `transpose` whose result has `n` elements (a reshape
    the compiler kept moves every byte on the chip: one that is free
    became a `bitcast`), or a `copy` of that size into another layout.
    A copy that changes only the memory space (`S(1)`: the compiler's
    own prefetch of a small operand into VMEM and its write-back, on
    either side of any kernel) is not one."""
    instrs = {m.group(1): m.groups()[1:] for m in
              map(_INSTR.match, text.splitlines()) if m}
    space = re.compile(r"S\(\d+\)")
    found = []
    for typ, dims, op, operand in instrs.values():
        if op not in ("reshape", "copy", "transpose") \
                or math.prod(map(int, dims.split(","))) != n:
            continue
        src = instrs.get(operand, ("",))[0]
        if op == "copy" and space.sub("", src) == space.sub("", typ):
            continue
        found.append((op, typ))
    return found


@pytest.mark.parametrize("master", [False, True],
                         ids=["fp32_param", "bf16_param_fp32_master"])
@pytest.mark.parametrize("leaf", ADAMW_LEAVES,
                         ids=lambda s: "x".join(map(str, s)))
def test_fused_adamw(for_chip, leaf, master):
    """The fused AdamW kernel blocks each leaf as the leaf lies in memory
    (ISSUE 31): compiled for the chip with its state donated, a leaf's
    update is the `fused_adamw` kernel and NO `reshape`, `copy` or
    `transpose` of the leaf's size: operands reach the kernel as the
    step holds them and its results are the new state, in place.
    Before, the kernel took every operand as `[n / 1024, 1024]` and four
    of the cell's six shapes (`[4096, 14336]`, `[14336, 4096]`, `[4096,
    4096]`, `[32768, 4096]`) failed this with SEVEN `reshape`s each
    (gradient, parameter and both moments in; parameter and both moments
    out: 78 ms of a 325 ms step; ledger, PR 29), as did both shard
    shapes, the expert stack and the long 1-D operand (`[n]` to `[n /
    1024, 1024]` is no bitcast either); only `[4096, 1024]` and `[4096]`
    passed."""
    text = _adamw_text(for_chip, leaf, master)
    assert "fused_adamw" in _kernels(text)
    assert _leaf_sized(text, math.prod(leaf)) == []


def test_fused_adamw_error_feedback(for_chip):
    """The same contract with the `ef` residual riding along (a fifth
    operand and result on the one block plan)."""
    leaf = (HIDDEN, 14336)
    text = _adamw_text(for_chip, leaf, master=False, ef=True)
    assert "fused_adamw" in _kernels(text)
    assert _leaf_sized(text, math.prod(leaf)) == []


@pytest.mark.parametrize("kv_heads", [32, 8], ids=["mha", "gqa"])
@pytest.mark.parametrize("width", [1, CHUNK], ids=["decode", "chunk"])
@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_paged_attention(for_chip, pool, width, kv_heads):
    """The serve step's kernel over the init_paged_cache layout
    [pages, layers, kv_heads, page_size, head_dim]: decode (C = 1) and
    chunked prefill (C = chunk), bf16 and int8 pools, MHA and GQA."""
    from paddle_tpu.ops.pallas.paged_attention import paged_attention
    quant = pool == "int8"
    pool_s = ((PAGES, LAYERS, kv_heads, PAGE_SIZE, HEAD_DIM),
              jnp.int8 if quant else jnp.bfloat16)
    scale_s = ((PAGES, LAYERS, kv_heads), jnp.float32)

    def f(q, kp, vp, pt, pos, ks, vs):
        return paged_attention(q, kp, vp, pt, pos, LAYERS - 1,
                               ks if quant else None,
                               vs if quant else None)
    text = for_chip(f, ((SLOTS, width, HEADS, HEAD_DIM), jnp.bfloat16),
                    pool_s, pool_s, ((SLOTS, PAGES_PER_SLOT), jnp.int32),
                    ((SLOTS,), jnp.int32), scale_s, scale_s)
    assert "paged_attention" in _kernels(text)


def _results(text, op):
    """(dims, layout) of every `op` instruction's result in HLO text,
    fused computations included; the layout as written, tiling cut."""
    pat = re.compile(r" = \w+\[([\d,]+)\]\{([\d,]+)[:}][^ ]* "
                     + re.escape(op) + r"\(")
    return [(tuple(map(int, m.group(1).split(","))), m.group(2))
            for m in map(pat.search, text.splitlines()) if m]


@pytest.mark.parametrize("width", [1, 32], ids=["decode", "admit"])
@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_pool_write_keeps_the_kernels_layout(for_chip, pool, width):
    """The dense pool write inside the serve scan (ISSUE 28), at
    dsllm7b_serve_chat_c24's widths (24 slots x 66 pages of 16 rows,
    32 kv heads of 128): 2 scan steps over 2 layers of
    ops.paged_kv_update then ops.paged_attention, each layer's rows
    made from the layer before, the pools donated.  The kernel takes
    its `pl.ANY` pools in the default dimension order only, so a write
    that makes XLA carry them in another order costs a pool-sized
    layout copy in front of every kernel call (1.65 GB a pool a layer
    at the cell's depth: 65 % of the cell's busy time up to PR 27).
    Held here: nothing pool-sized or layer-slice-sized is copied,
    sliced or written back along the layer axis; the only pool-sized
    work is the in-place scatter, on the default order."""
    from paddle_tpu import ops
    slots, p_slot, layers, steps = 24, 66, 2, 2
    pages = 1 + slots * p_slot
    quant = pool == "int8"

    def f(kp, vp, ks, vs, table, pos, x):
        def body(carry, _):
            kp, vp, ks, vs, pos, x = carry
            for layer in range(layers):
                k, v = x * 0.5, x * 2.0
                kp, vp, ks, vs = ops.paged_kv_update(
                    kp, vp, ks, vs, table, pos, k, v, layer)
                x = ops.paged_attention(x, kp, vp, table, pos, layer,
                                        ks, vs)
            return (kp, vp, ks, vs, pos + width, x), _sum32(x)
        (kp, vp, ks, vs, _, _), ys = jax.lax.scan(
            body, (kp, vp, ks, vs, pos, x), None, length=steps)
        return kp, vp, ks, vs, ys

    pool_s = ((pages, layers, HEADS, PAGE_SIZE, HEAD_DIM),
              jnp.int8 if quant else jnp.bfloat16)
    scale_s = ((pages, layers, HEADS), jnp.float32) if quant else None
    text = for_chip(f, pool_s, pool_s, scale_s, scale_s,
                    ((slots, p_slot), jnp.int32), ((slots,), jnp.int32),
                    ((slots, width, HEADS, HEAD_DIM), jnp.bfloat16),
                    donate=(0, 1, 2, 3))
    assert "paged_attention" in _kernels(text)
    layer_elems = pages * HEADS * PAGE_SIZE * HEAD_DIM
    big = {layer_elems, layer_elems * layers}
    for op in ("copy", "slice", "dynamic-update-slice", "transpose"):
        found = [r for r in _results(text, op)
                 if math.prod(r[0]) in big]
        assert not found, f"pool-sized {op}: {found}"
    scatters = [r for r in _results(text, "scatter")
                if math.prod(r[0]) in big]
    # K and V, a layer each, in the scan body
    assert len(scatters) == 2 * layers and all(
        r == (pool_s[0], "4,3,2,1,0") for r in scatters), scatters


@pytest.mark.parametrize("width", [1, 32], ids=["decode", "admit"])
def test_latent_pool_write_and_attention(for_chip, width):
    """The latent (MLA) pool at sarvam105b_serve_chat_c64's geometry (64
    slots x 256 pages of 16 rows, 64 heads, rank 512 + rope 64; 2 layers
    here): the scatter of the step's rows, then the absorbed attention
    (ISSUE 33).  The COMPILED program holds the `latent_attention` kernel
    and nothing of the XLA walk it replaces: no loop over blocks of
    gathered rows, no [slots, block rows, 576] gather or copy.  This is
    also where Mosaic answers for the 576-wide rows (whole pages through
    BlockSpecs: it refuses the kernel's own copies of them, see
    ops/pallas/latent_attention.py) and for the page table, the work list
    and the depths in SMEM."""
    from paddle_tpu import ops
    heads, rank, rope, slots, p_slot, layers = 64, 512, 64, 64, 256, 2
    pages = 1 + slots * p_slot

    def f(pool, table, pos, rows, q_lat, q_rope):
        pool = ops.latent_kv_update(pool, table, pos, rows, 1)
        return pool, ops.latent_paged_attention(q_lat, q_rope, pool, table,
                                                pos, 1, 0.1)
    bf = jnp.bfloat16
    text = for_chip(f, ((pages, layers, PAGE_SIZE, rank + rope), bf),
                    ((slots, p_slot), jnp.int32), ((slots,), jnp.int32),
                    ((slots, width, rank + rope), bf),
                    ((slots, width, heads, rank), bf),
                    ((slots, width, heads, rope), bf), donate=(0,))
    assert "latent_attention" in _kernels(text)
    assert "scatter" in text and " while(" not in text
    walk = slots * ops.LATENT_BLOCK_ROWS * (rank + rope)
    for op in ("gather", "copy"):
        found = [r for r in _results(text, op) if math.prod(r[0]) == walk]
        assert not found, f"a block of gathered rows: {op} {found}"


def test_dropless_experts_grouped_product(for_chip):
    """The share-aware expert layer's dispatch at the cell's widths (hidden
    4096, experts 2048 wide, 8 of 128 a token, 8 held here): the grouped
    product is the TPU's own ragged-dot kernel, and its group sizes are
    32-bit although the package turns jax_enable_x64 on."""
    import paddle_tpu  # noqa: F401  (x64 on, as every program of the repo)
    from paddle_tpu.incubate.distributed.models.moe import dropless_experts
    held, d, f_, k, tokens = 8, 4096, 2048, 8, 256

    def f(x, logits, w1, w2):
        topv, topi = jax.lax.top_k(logits, k)
        return dropless_experts(x, topi, topv, w1, w2, "swiglu", first=16)
    bf = jnp.bfloat16
    text = for_chip(f, ((tokens, d), bf), ((tokens, 128), jnp.float32),
                    ((held, d, 2 * f_), bf), ((held, f_, d), bf))
    assert text.count("ragged-dot") >= 2


@pytest.mark.parametrize("width", [4, 32], ids=["block", "admit"])
def test_paged_attention_under_the_block_mask(for_chip, width):
    """The kernel at sdar30b_serve_gen_c64's geometry (64 slots x 66 pages
    of 16 rows, 32 query heads of 128 on 4 kv heads: group 8), block-causal
    over blocks of 4: a decode pass's 4 lanes and an admission step's 32."""
    from paddle_tpu.ops.pallas.paged_attention import paged_attention
    slots, pages_per_slot, kv_heads, layers = 64, 66, 4, 7
    pool_s = ((1 + slots * pages_per_slot, layers, kv_heads, PAGE_SIZE,
               HEAD_DIM), jnp.bfloat16)

    def f(q, kp, vp, pt, pos):
        return paged_attention(q, kp, vp, pt, pos, layers - 1,
                               block_length=4)
    text = for_chip(f, ((slots, width, HEADS, HEAD_DIM), jnp.bfloat16),
                    pool_s, pool_s, ((slots, pages_per_slot), jnp.int32),
                    ((slots,), jnp.int32))
    assert "paged_attention" in _kernels(text)


def test_dropless_experts_all_held(for_chip):
    """The softmax-routed expert layer with every expert of the router held
    (128 experts 768 wide behind hidden 2048, 8 a token, a decode pass's
    256 lanes): still the grouped-product kernel."""
    import paddle_tpu  # noqa: F401  (x64 on, as every program of the repo)
    from paddle_tpu.incubate.distributed.models.moe import dropless_experts
    held, d, f_, k, tokens = 128, 2048, 768, 8, 256

    def f(x, probs, valid, w1, w2):
        topv, topi = jax.lax.top_k(probs, k)
        return dropless_experts(x, topi, topv, w1, w2, "swiglu", valid=valid)
    bf = jnp.bfloat16
    text = for_chip(f, ((tokens, d), bf), ((tokens, held), jnp.float32),
                    ((tokens,), jnp.bool_), ((held, d, 2 * f_), bf),
                    ((held, f_, d), bf))
    assert text.count("ragged-dot") >= 2


def _computations(text):
    """{name: its lines} of every computation of an HLO module's text."""
    found, name = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.-]+) \(.*\{$", line)
        if m:
            name = m.group(1)
            found[name] = []
        elif name is not None:
            found[name].append(line)
    return found


def _closure(computations, root):
    """The text of a computation and of every one it calls, however deep
    (fusions, loops, the grouped product's own computations)."""
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen or name not in computations:
            continue
        seen.add(name)
        for line in computations[name]:
            todo.extend(re.findall(r"%([\w.-]+)", line.split(" = ", 1)[-1]))
    return "\n".join(line for name in sorted(seen)
                     for line in computations[name])


def _row_sized(text, rows, least):
    """Dims of every instruction result `rows` long and at least `least`
    wide: a buffer of sorted rows."""
    pat = re.compile(r" = \w+\[(%d),(\d+)\]\{" % rows)
    return [(int(m.group(1)), int(m.group(2)))
            for m in map(pat.search, text.splitlines())
            if m and int(m.group(2)) >= least]


@pytest.mark.parametrize("d, f_, held", [(6144, 2048, 16), (2048, 768, 128)],
                         ids=["kexaone236b", "sdar30b"])
def test_dropless_experts_ladder_at_an_admission_step(for_chip, d, f_, held):
    """The expert layer at the expert cells' REAL admission geometry (2,048
    tokens, 8 of a router 128 wide; kexaone236b_serve_mixed_c64 holds 16
    experts 2048 wide behind hidden 6144, sdar30b_serve_gen_c64 all 128, 768
    wide behind 2048; a `valid` mask): one conditional over the ladder's
    three rungs.  The SHORTEST rung (a sixteenth: what a window step takes)
    holds no buffer of S*k rows at all: its rows come and go by R; the
    MIDDLE one gathers, multiplies and activates R rows and only its combine
    is the full-length program's gather of S*k rows (cheaper than a
    scatter-add above an eighth of S*k: CHANGES.md, PR 35); every rung runs
    the grouped product twice; and the conditional copies neither expert
    stack (0.8 and 0.4 GB a layer on kexaone)."""
    import paddle_tpu  # noqa: F401  (x64 on, as every program of the repo)
    from paddle_tpu.incubate.distributed.models.moe import (
        dropless_experts, sorted_lengths)
    k, tokens = 8, 2048
    n = tokens * k
    assert sorted_lengths(n) == (1024, 4096, n)

    def f(x, scores, valid, w1, w2):
        topv, topi = jax.lax.top_k(scores, k)
        return dropless_experts(x, topi, topv, w1, w2, "swiglu", valid=valid)
    bf = jnp.bfloat16
    text = for_chip(f, ((tokens, d), bf), ((tokens, 128), jnp.float32),
                    ((tokens,), jnp.bool_), ((held, d, 2 * f_), bf),
                    ((held, f_, d), bf))
    conditionals = re.findall(r"conditional\(.*branch_computations=\{([^}]*)\}",
                              text)
    assert len(conditionals) == 1, conditionals
    branches = [b.strip().lstrip("%") for b in conditionals[0].split(",")]
    assert len(branches) == 3
    computations = _computations(text)
    low, middle, top = (_closure(computations, b) for b in branches)
    for branch in (low, middle, top):
        assert branch.count("ragged-dot") >= 2
    assert _row_sized(low, 1024, f_) and not _row_sized(low, n, f_)
    assert not _row_sized(low, 4096, f_)
    # the middle rung: the combine's rows alone are S*k long (as wide as the
    # stream), nothing the dispatch or the activation make
    assert _row_sized(middle, 4096, f_)
    assert {width for _, width in _row_sized(middle, n, f_)} <= {d}
    assert (n, 2 * f_) in _row_sized(top, n, f_)
    stacks = {held * d * 2 * f_, held * f_ * d}
    copied = [r for r in _results(text, "copy") + _results(text, "copy-start")
              if math.prod(r[0]) in stacks]
    assert not copied, f"an expert stack is copied: {copied}"


@pytest.mark.parametrize("width", [1, 32], ids=["decode", "admit"])
def test_paged_attention_over_a_ring(for_chip, width):
    """The kernel with a WINDOW at kexaone236b_serve_mixed_c64's geometry
    (64 slots, each a ring of 11 pages of 16 rows in a pool of 4 sliding
    layers, 64 query heads of 128 on 8 kv heads: group 8, window 128): the
    walk starts at the window's first page, the table entry is the logical
    page mod the ring, all in the scalar core."""
    from paddle_tpu import ops
    from paddle_tpu.ops.pallas.paged_attention import paged_attention
    slots, kv_heads, heads, layers, window = 64, 8, 64, 4, 128
    ring = ops.ring_pages(window, 32, PAGE_SIZE)
    assert ring == 11
    pool_s = ((slots * ring, layers, kv_heads, PAGE_SIZE, HEAD_DIM),
              jnp.bfloat16)

    def f(q, kp, vp, pt, pos):
        return paged_attention(q, kp, vp, pt, pos, layers - 1, window=window)
    text = for_chip(f, ((slots, width, heads, HEAD_DIM), jnp.bfloat16),
                    pool_s, pool_s, ((slots, ring), jnp.int32),
                    ((slots,), jnp.int32))
    assert "paged_attention" in _kernels(text)


@pytest.mark.parametrize("mixed", [False, True], ids=["decode", "admit"])
def test_window_model_step_programs_copy_neither_pool(one_chip, for_chip,
                                                      mixed):
    """BOTH step programs of ContinuousBatcher over a tiny model with KINDS
    of layer (L L L G L, window 128, 8 query heads of 128 on 2 kv heads, 4
    of 16 experts; 4 slots x 512 rows, pages of 16, a ring of 11), compiled
    for the described chip with the kernels in: the full layers' pool and
    the window layers' pool are each indexed whole by (page, layer), so the
    compiled programs hold no pool-sized (or pool-layer-sized) `copy`,
    `slice` or `dynamic-update-slice` of either (PR 28's rule, for two
    pools), and the `paged_attention` kernel serves both kinds."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatcher
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(5)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        num_hidden_layers=5, num_attention_heads=8, num_key_value_heads=2,
        head_dim=HEAD_DIM, use_qk_norm=True, max_position_embeddings=1024,
        rope_theta=1e6, dtype="bfloat16",
        layer_types=("sliding_attention",) * 3
        + ("full_attention", "sliding_attention"), sliding_window=128,
        rope_layer_types=("sliding_attention",), first_k_dense_replace=1,
        moe_gate="sigmoid", moe_num_experts=4, moe_first_expert=4,
        moe_router_width=16, moe_top_k=3, moe_intermediate_size=128,
        moe_shared_experts=1, moe_routed_scaling=2.5, moe_router_bias=True))
    model.eval()
    bat = ContinuousBatcher(model, max_batch_size=4, max_len=512)
    assert bat.ring_pages == 11 and bat.page_size == PAGE_SIZE
    width, steps = (bat.prefill_chunk, bat.admit_steps) if mixed \
        else (1, bat.chunk)
    fn = bat._step_fn(width, steps, record=False)
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (bat._param_vals(), *bat._carry_args()))
    text = fn.lower(*args).compile().as_text()
    assert "paged_attention" in _kernels(text)
    big = set()
    for name in ("k", "k_window"):
        pages, layers, *rest = bat._cache[name].shape
        big |= {pages * math.prod(rest), pages * layers * math.prod(rest)}
    for op in ("copy", "slice", "dynamic-update-slice", "transpose"):
        found = [r for r in _results(text, op) if math.prod(r[0]) in big]
        assert not found, f"pool-sized {op}: {found}"
    scatters = [r for r in _results(text, "scatter")
                if math.prod(r[0]) in big]
    # K and V of each of the five layers, on the default order (the
    # compiler drops the one-layer full pool's unit axis)
    assert len(scatters) == 2 * 5 and all(
        r[1] == ",".join(map(str, reversed(range(len(r[0])))))
        for r in scatters), sorted(set(scatters))
