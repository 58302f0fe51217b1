"""The Pallas kernels and the serving step compile for a TPU v5e — checked
on the CPU host.

No chip is needed: ``tools.tpu_aot`` lowers against
``jax.experimental.topologies``' description of a v5e host and runs the
installed libtpu's compiler, Mosaic included, with the kernel path forced
to Mosaic through the kernels' explicit ``path=`` argument.  This keeps
"the kernels compile at real shapes" and "the serving step leaves its page
pools where they are" true on every PR at no chip cost; that they
*compute* the right thing on the chip is ``chip_smoke.py``.
"""
import re

import pytest

pytest.importorskip("libtpu")

from tools import tpu_aot  # noqa: E402


@pytest.fixture(scope="module")
def devices():
    return tpu_aot.topology_devices()


@pytest.fixture(scope="module")
def serve_small(devices):
    """The GPT serving step at a pool of 64 pages, compiled once."""
    return tpu_aot.lower_serve_step(
        devices, num_pages=64, max_batch_size=16, chunk_len=128).compile()


@pytest.fixture(scope="module")
def hybrid_compiled(devices):
    return tpu_aot.lower_hybrid_serve_step(devices).compile()


@pytest.fixture(scope="module")
def moe_compiled(devices):
    return tpu_aot.lower_moe_window_serve_step(devices).compile()


def test_kernels_compile_for_v5e(devices):
    device = devices[0]
    assert (device.platform, device.device_kind) == ("tpu", "TPU v5 lite")
    compiled = tpu_aot.compile_kernels(device)
    assert set(compiled) == {"ragged_q128", "ragged_q1", "ragged_h4",
                             "ragged_stacked", "flash_hd64", "flash_hd128"}
    for name, c in compiled.items():
        assert "tpu_custom_call" in c.as_text(), \
            f"{name}: no Mosaic kernel in the compiled program"


def test_serve_step_moves_no_page_pool(serve_small):
    """The compiled serving step touches its two donated page pools with
    one in-place scatter each and the kernel, and nothing else: no copy,
    slice, update-slice or fusion produces a pool, or one layer's pages,
    alone or inside a tuple-shaped result (as the layer scan's xs/ys did:
    4 x 64 MiB a layer and two 1.5 GiB copies a step at the benchmark's
    1024 pages)."""
    pages, compiled = 64, serve_small
    text = compiled.as_text()
    from paddle_tpu.models.gpt import GPT_CONFIGS

    cfg = GPT_CONFIGS["gpt3-1.3b"]              # pages of 16 positions
    L, H, hd = cfg.num_layers, cfg.num_heads, cfg.head_dim
    pool_like = {f"[{L},{pages},16,{H},{hd}]", f"[1,{pages},16,{H},{hd}]",
                 f"[{pages},16,{H},{hd}]"}

    # fused computation name -> the opcode of its ROOT
    roots, current = {}, None
    for line in text.splitlines():
        head = re.match(r"%(\S+) \(.*\) -> .* \{$", line)
        if head:
            current = head.group(1)
        root = re.match(r"\s+ROOT %\S+ = \S+ ([\w-]+)\(", line)
        if root and current:
            roots[current] = root.group(1)

    # opcodes that hand a buffer on without moving it (the layer loop and
    # its tuples carry the pools), and the scatter inside the two fusions
    free = {"parameter", "get-tuple-element", "bitcast", "tuple", "while",
            "scatter"}
    movers, scatter_fusions = [], []
    for line in text.splitlines():
        # the whole result type, so that a pool inside a tuple-shaped
        # result (a multi-output fusion) is seen too
        m = re.match(r"\s+(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\(", line)
        if (not m or m.group(3) in free
                or not any(shape in m.group(2) for shape in pool_like)):
            continue
        called = re.search(r"calls=%([^\s,]+)", line)
        if (m.group(3) == "fusion" and called
                and roots.get(called.group(1)) == "scatter"):
            scatter_fusions.append(m.group(1))
        else:
            movers.append(line.strip()[:160])
    assert not movers, "pool-sized movers:\n" + "\n".join(movers)
    assert len(scatter_fusions) == 2, scatter_fusions

    one_layer = pages * 16 * H * hd * 2
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def test_gpt_serve_step_keeps_its_temporaries_behind_the_model_interface(
        devices):
    """The dense family is served through the engine's model interface
    (``serving/model.py``) and its compiled step holds what it held: at
    the benchmark's 1024 pages and 16 rows 5,852,881,408 B of arguments
    (the 5,852,876,800 B of weights, pools and batch, one 4 KiB tile
    for the ``[16, 6]`` sampling table and 512 B for the ``[16]`` ids of
    the step before, PR 34) and one Mosaic call — the
    equal-heads kernel, which the grouped / selected mode must not reach.
    XLA plans 1,677,312 B of temporaries in HBM: the 1,032,192 B of PR
    27's program, 96,768 B that the kernel's work list brought (PR 29; by
    the compiler's memory report the block of small arrays grew by three
    16 KiB slots, 496.5 to 544.5 KiB — the list's rows, tiles and count,
    built once a step — and the loops hold 36 more scalar slots) and
    451,584 B for sampling on the device (PR 31: the rows' ids, the
    ``cond``'s operands and what the drawing branch keeps in HBM) and
    96,768 B where the packed tokens are no longer an operand the
    embedding reads in place but the result of resolving the pending ones
    (PR 34: a gather and a select over ``[143]`` ids).  The
    padded queries and the kernel's output are in the chip's fast memory,
    26.85 MiB as before, which this figure does not count.  The draw is
    compiled once, in one branch of one ``conditional``, and nothing in
    the step sorts (a sort of 50,304 entries takes this compiler 22 s,
    the whole step 3 s)."""
    compiled = tpu_aot.lower_serve_step(
        devices, num_pages=1024, max_batch_size=16, chunk_len=128).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert mem.temp_size_in_bytes == 1677312
    assert mem.argument_size_in_bytes == 5852881408
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert (text.count(" conditional("), text.count(" sort(")) == (1, 0)


def test_hybrid_serve_step_compiles_for_v5e_and_moves_no_pool(
        hybrid_compiled):
    """The sparse-plus-lightning step at the benchmark cell's shapes
    (published widths, 8 layers, 16 rows, chunks of 512, 8192 pages of
    64): Mosaic accepts both new kernels, every state pool is donated and
    aliased, nothing re-lays or copies a key/value pool, and the plan
    fits the chip: 6,950,199,808 B of arguments and 841,193,472 B of
    temporaries (840,322,560 B before the attention kernel's items held 8
    listed pages each, PR 36: the work list is 2,856 items with their 8
    page slots and a count each where it was 22,848 items of one page,
    built by the step, which sums it into two int32 behind the ids;
    6,950,195,200 B and 839,838,720 B before the step
    sampled its ``[16, 73472]`` logits itself, PR 31: 4,096 B for the
    table, 516,096 B for the ids and the drawing branch; PR 34's
    ``[16]`` ids of the step before are 512 B more of arguments, and the
    plan with the resolved tokens packs 32,256 B tighter)."""
    from paddle_tpu.models.hybrid import HYBRID_CONFIGS

    compiled = hybrid_compiled
    text, mem = compiled.as_text(), compiled.memory_analysis()
    cfg = HYBRID_CONFIGS["minicpm-sala-8l"]
    # one Mosaic call a layer: 2 sparse, 6 lightning
    assert text.count('custom_call_target="tpu_custom_call"') == \
        cfg.num_layers
    pools = 2 * 2 * 8192 * 2 * 64 * 128 * 2 + 2 * 8192 * 4 * 2 * 128 * 2 \
        + 6 * 16 * 32 * 128 * 128 * 4
    assert mem.alias_size_in_bytes >= pools
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12e9
    assert (mem.argument_size_in_bytes, mem.temp_size_in_bytes) == \
        (6950199808, 841193472)
    assert text.count(" conditional(") == 1
    pool = "bf16[2,8192,2,64,128]"
    movers = [line.strip()[:160] for line in text.splitlines()
              if re.match(rf"\s+(?:ROOT )?%\S+ = {re.escape(pool)}\S* "
                          rf"(copy|transpose|dynamic-update-slice)\(", line)]
    assert not movers, "key/value pool movers:\n" + "\n".join(movers)


def test_ssm_serve_step_compiles_for_v5e_and_fits_the_chip(devices):
    """The parallel-mixer step (a state-space mixer beside grouped-query
    attention in every block) at the benchmark cell's shapes — published
    widths, 6 layers, the whole 261,120-id vocabulary, 64 rows, chunks of
    128, 131,072 cached positions in 256 pages of 512: Mosaic accepts the selective-scan
    kernel and the grouped-heads attention kernel at a group of 5 (one
    call each: the layers are one ``lax.scan``), all four state pools are
    donated and aliased, and the plan fits the chip before any chip time
    is spent: 13,742,309,888 B of arguments (10.51 GB of weights, 1.61 GB
    of pages, 1.62 GB of window and scan state, and since PR 34 the 512 B
    of the ``[64]`` ids of the step before) and 128,745,984 B of
    temporaries (96,768 B of them for the resolved tokens, PR 34; 182,784 B
    for the attention kernel's work list with a count an item, PR 36: at
    pages of 512 an item is one page as before, fetched by the kernel's
    own copies where a ``BlockSpec`` brought it), with the ``[64, 261120]`` float32 logits 66,846,720 B
    more: 13.94 GB of the chip's 17.18."""
    compiled = tpu_aot.lower_ssm_serve_step(devices).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    pools = 2 * 6 * 256 * 4 * 512 * 128 * 2 + 6 * 64 * 3 * 5120 * 2 \
        + 6 * 64 * 32 * 128 * 256 * 4
    assert mem.alias_size_in_bytes == pools == 3233021952
    assert (mem.argument_size_in_bytes, mem.temp_size_in_bytes) == \
        (13742309888, 128745984)
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 68e6
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 2 ** 34
    assert text.count(" conditional(") == 1
    pool = "bf16[6,256,4,512,128]"
    movers = [line.strip()[:160] for line in text.splitlines()
              if re.match(rf"\s+(?:ROOT )?%\S+ = {re.escape(pool)}\S* "
                          rf"(copy|transpose|dynamic-update-slice)\(", line)]
    assert not movers, "key/value pool movers:\n" + "\n".join(movers)
    state = "f32[6,64,32,128,256]"
    movers = [line.strip()[:160] for line in text.splitlines()
              if re.match(rf"\s+(?:ROOT )?%\S+ = {re.escape(state)}\S* "
                          rf"(copy|transpose|dynamic-update-slice|fusion)\(",
                          line)]
    assert not movers, "scan state movers:\n" + "\n".join(movers)


def test_moe_window_serve_step_compiles_for_v5e_and_fits_the_chip(
        moe_compiled):
    """The sparse-expert step with sliding-window layers at the benchmark
    cell's shapes — published widths, 8 layers, 64 of 256 experts, 25,088
    ids, 64 rows, chunks of 1024 (1,088 packed tokens), 2,240 full-layer and
    256 window-layer pages of 512: Mosaic accepts the grouped expert product
    (two calls in each of the 7 sparse layers) and the grouped-heads
    attention kernel at groups of 6 and of 9 with the window's lower edge
    (8 calls), all four page pools are donated and aliased, no layer's
    experts are sliced out of their stack (384 MB a matrix: what the first
    compile of this step planned), and the plan fits the chip before any
    chip time is spent: 9.37 GB of weights, 2.35 GB of full-layer pages and
    0.81 GB of window-layer pages among 12.52 GB of arguments."""
    compiled = moe_compiled
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count('custom_call_target="tpu_custom_call"') == 8 + 2 * 7
    pools = 2 * 2 * 2240 * 2 * 512 * 128 * 2 + 2 * 6 * 256 * 2 * 512 * 128 * 2
    assert mem.alias_size_in_bytes == pools == 3154116608
    weights = 4_683_660_288 * 2
    assert weights + pools < mem.argument_size_in_bytes < weights + pools \
        + 2 ** 20
    assert mem.temp_size_in_bytes < 1.2e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 2 ** 34
    movers = [line.strip()[:160] for line in text.splitlines()
              if re.match(r"\s+(?:ROOT )?%\S+ = bf16\[(2,2240|6,256),2,512,"
                          r"128\]\S* (copy|transpose|dynamic-update-slice)\(",
                          line)]
    assert not movers, "key/value pool movers:\n" + "\n".join(movers)
    sliced = [line.strip()[:160] for line in text.splitlines()
              if re.match(r"\s+(?:ROOT )?%\S+ = bf16\[64,(3072,1024|1024,"
                          r"3072)\]", line)]
    assert not sliced, "a layer's experts out of their stack:\n" \
        + "\n".join(sliced)
    # the expert kernels move their own rows: no array of the sorted
    # buffer's 14,976 rows of 3072, and no [tokens, choices, 3072] of
    # results gathered back, is built around them
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.match(r"\s+(?:ROOT )?%\S+ = \w+\[(14976,3072|"
                         r"108[78],10,3072)\]", line)]
    assert not moved, "token rows moved outside the expert kernels:\n" \
        + "\n".join(moved)


# ------------------------------------------------ the instruction table
# (paddle_tpu/observability/compile_watchdog.py) on the TPU compiler's text


def _table(compiled):
    from paddle_tpu.observability import compile_watchdog as cw

    table = cw.parse_instruction_table(compiled.as_text())
    # XLA names a Mosaic call after the kernel's own scope; the
    # ``get-tuple-element``s that hand on the results of a kernel with
    # several carry its path under other names
    calls = {n: p for n, p in table.items()
             if cw.leaf_primitive(p) == "pallas_call"
             and n.startswith(cw.named_scopes(p)[-1])}
    return cw, table, calls


def _scoped_share(cw, table):
    fusions = [p for n, p in table.items() if "fusion" in n]
    return sum(bool(cw.named_scopes(p)) for p in fusions) / len(fusions)


def test_serve_step_table_names_kernel_scatters_and_most_fusions(
        serve_small):
    """The names a trace of the GPT serving cell shows (``fusion.196``,
    ``ragged_paged_attention.3``) are this table's keys: the one Mosaic
    call under ``attn``, both page scatters under ``kv_write``, and at
    least 80% of the top-level fusions under some named scope (the rest:
    the layer scan's own slicing of the stacked biases, and what XLA made
    without metadata)."""
    cw, table, calls = _table(serve_small)
    assert len(calls) == 1
    (name, path), = calls.items()
    assert name.startswith("ragged_paged_attention")
    assert cw.named_scopes(path)[-2:] == ("attn", "ragged_paged_attention")
    scatters = [n for n, p in table.items()
                if "kv_write" in cw.named_scopes(p)
                and cw.leaf_primitive(p) == "scatter"]
    assert len(scatters) == 2 and all("fusion" in n for n in scatters)
    assert _scoped_share(cw, table) >= 0.8
    scopes = {s for p in table.values() for s in cw.named_scopes(p)}
    assert {"attn", "kv_write", "mlp", "lm_head", "embed", "work_list",
            "batch_view", "pending", "sample"} <= scopes
    assert {cw.pass_of(p) for p in table.values()} == {None}


def test_train_step_table_separates_the_replayed_forward(devices):
    """Under full recomputation the flash forward kernel is in the
    compiled train step twice: once as the ``forward`` pass and once as
    the ``recompute`` the backward pass asks for; the two backward
    kernels are ``backward``, the optimizer is no pass at all."""
    cw, table, calls = _table(tpu_aot.lower_train_step(devices[:1]).compile())
    passes = {}
    for name, path in calls.items():
        kernel = cw.named_scopes(path)[-1]
        assert name.startswith(kernel)
        passes.setdefault(kernel, []).append(cw.pass_of(path))
    assert sorted(passes.pop("flash_fwd")) == ["forward", "recompute"]
    assert passes == {"flash_bwd_dkdv": ["backward"],
                      "flash_bwd_dq": ["backward"]}
    assert _scoped_share(cw, table) >= 0.9
    optimizer = [p for p in table.values()
                 if "optimizer" in cw.named_scopes(p)]
    assert len(optimizer) > 20
    assert {cw.pass_of(p) for p in optimizer} == {None}
    replayed = {s for p in table.values() if cw.pass_of(p) == "recompute"
                for s in cw.named_scopes(p)}
    assert {"forward_backward", "attn", "mlp"} <= replayed


def test_hybrid_and_moe_step_tables_name_selection_and_experts(
        hybrid_compiled, moe_compiled):
    cw, table, calls = _table(hybrid_compiled)
    under = lambda scope: {n: p for n, p in table.items()
                           if scope in cw.named_scopes(p)}
    assert any(n.startswith("sort") for n in under("select"))
    assert any(cw.leaf_primitive(p) == "dot_general"
               for p in under("select").values())
    assert under("work_list") and not set(under("work_list")) & set(
        under("select"))
    assert sorted(cw.named_scopes(p)[-2] for p in calls.values()) == \
        ["sparse_attn"] * 2 + ["state_write"] * 6
    assert _scoped_share(cw, table) >= 0.8

    cw, table, calls = _table(moe_compiled)
    experts = [p for p in calls.values() if "experts" in cw.named_scopes(p)]
    assert len(experts) == 2 * 7 and len(calls) == 8 + 2 * 7
    outside = [n for n, p in table.items() if n not in calls
               and "experts" in cw.named_scopes(p) and "fusion" in n]
    assert len(outside) > 7          # the rank, the layout, two scatters
    assert _scoped_share(cw, table) >= 0.8


def test_short_conv_serve_step_compiles_for_v5e_and_fits_the_chip(devices):
    """The short-convolution step with sigmoid-routed experts at the
    benchmark cell's shapes — published widths, 12 layers (9 convolution
    and 3 attention, 2 dense and 10 expert), all 32 experts, the whole
    65,536-id vocabulary, 64 rows, chunks of 128, 256 pages of 512: Mosaic
    accepts the grouped-heads attention kernel at heads of 64 in pool rows
    of 128 lanes (3 calls) and the grouped expert product (two calls in
    each of the 10 expert layers); both page pools and the windows are
    donated and aliased, no pool is re-laid around the scatters of a
    token's rows (with rows of 64 XLA re-laid both pools, 1.2 GB of
    temporaries), and the plan fits the chip: 7.86 GB of weights, 1.61 GB
    of pages and 4.7 MB of windows.  The scopes the cell's readers read
    name the instructions."""
    compiled = tpu_aot.lower_short_conv_serve_step(devices).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count('custom_call_target="tpu_custom_call"') == 3 + 2 * 10
    pools = 2 * 3 * 256 * 8 * 512 * 128 * 2 + 9 * 64 * 2 * 2048 * 2
    assert mem.alias_size_in_bytes == pools == 1615331328
    weights = 3_928_728_256 * 2
    assert weights + pools < mem.argument_size_in_bytes < weights + pools \
        + 2 ** 20
    assert mem.temp_size_in_bytes < 0.3e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 2 ** 34
    movers = [line.strip()[:160] for line in text.splitlines()
              if re.match(r"\s+(?:ROOT )?%\S+ = bf16\[(3,256,8,512,128|"
                          r"\d+,\d+,6144,512)\]\S* (copy|transpose|"
                          r"dynamic-update-slice)\(", line)]
    assert not movers, "key/value pool movers:\n" + "\n".join(movers)
    cw, table, calls = _table(compiled)
    scopes = lambda p: cw.named_scopes(p)
    assert len([p for p in calls.values() if "experts" in scopes(p)]) == 20
    assert len([p for p in calls.values() if "attn" in scopes(p)]) == 3
    for scope in ("short_conv", "conv_window", "qk_norm", "kv_write",
                  "router", "mlp", "lm_head", "embed"):
        assert any(scope in scopes(p) for p in table.values()), scope
    assert _scoped_share(cw, table) >= 0.8


def test_tiled_attention_body_lowers_no_larger_than_twice_one_tile_body(
        devices):
    """The tile-packed attention kernel reads up to 8 tiles of 16 query
    slots of one row in an item through one pair of products over their
    flattened rows; a body written out once per tile would multiply what
    every call of a step traces, lowers and compiles.  At the agent cell's
    window-layer shapes (132 tiles of 16 slots: 64 rows beside a chunk of
    1024; 18 query heads over 2 key/value heads; 6 layers of 256 pages of
    512, tables 4 wide) the Mosaic payload stays within twice the 16,992
    characters of the one-tile body at tiles of 128 it replaced."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.kernels import dispatch
    from paddle_tpu.kernels.paged_attention import ragged_paged_attention

    one = SingleDeviceSharding(devices[0])
    bf16, i32 = jnp.bfloat16, jnp.int32
    pool = ((6, 256, 2, 512, 128), bf16)
    args = tpu_aot._on(one, ((132, 16, 18, 128), bf16), pool, pool,
                       ((64, 4), i32), ((64,), i32), ((64,), i32),
                       ((132,), i32), ((132,), i32), ((), i32))

    def call(q, kp, vp, tables, q_lens, ctx_lens, rows, index, layer):
        return ragged_paged_attention(
            q, kp, vp, tables, q_lens, ctx_lens, path=dispatch.MOSAIC,
            layer=layer, selected=(None, 2 ** 30), window=512,
            q_tiles=(rows, index))

    text = jax.jit(call).lower(*args).as_text()
    payloads = re.findall(r'backend_config = "([^"]*)"', text)
    assert len(payloads) == 1
    assert 10_000 < len(payloads[0]) <= 34_000, len(payloads[0])


def test_latent_attention_kernel_compiles_at_the_cells_shapes(devices):
    """The absorbed latent kernel at the long-context cell's shapes, every
    way it reads its rows: a full layer's 527 queries of 128 heads, each
    reading 2,048 listed positions of float32 slabs [8, 128] through a
    16 x 544 page table (the list's [8, 2048] blocks in scalar memory), a
    sliding layer's 64 heads over a 513-position window of slabs [16,
    128], and the full layer's queries in 49 tiles of 16 (the step's 16
    rows and 33 tiles of its chunk of 512) through the per-row call and the
    staged one, whose VMEM plan is printed: two blocks of 2,048 staged
    positions, each query's picked rows, and the tile's queries and
    softmax state."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.kernels import dispatch
    from paddle_tpu.kernels import latent_attention as la

    one = SingleDeviceSharding(devices[0])
    sd = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one)
    N, B, max_pages, i32 = 527, 16, 544, jnp.int32
    G = la.STAGED_TILE
    NT = B + -(-N // G)
    for slab, heads, width, latent, rows, tiles in (
            (8, 128, 640, 512, 2048, False), (16, 64, 1152, 1024, None, False),
            (8, 128, 640, 512, 2048, True)):
        args = [sd((N, heads, width), jnp.bfloat16),
                sd((2, 256, 64, slab, 128), jnp.float32),
                sd((B, max_pages), i32), sd((N,), i32), sd((N,), i32)]
        if tiles:
            kernel = lambda q, pool, t, r, c, lst, tr, tt: la.latent_attention(
                q, pool, t, r, c, layer=1, scale=0.07, latent=512,
                listed=lst, tiles=(tr, tt), path=dispatch.MOSAIC)
            args += [sd((N, rows), i32), sd((NT,), i32), sd((NT, G), i32)]
        elif rows:
            kernel = lambda q, pool, t, r, c, lst: la.latent_attention(
                q, pool, t, r, c, layer=1, scale=0.07, latent=512,
                listed=lst, path=dispatch.MOSAIC)
            args.append(sd((N, rows), i32))
        else:
            kernel = lambda q, pool, t, r, c, first: la.latent_attention(
                q, pool, t, r, c, layer=1, scale=0.06, latent=1024,
                first=first, window=513, path=dispatch.MOSAIC)
            args.append(sd((N,), i32))
        text = jax.jit(kernel).lower(*args).compile().as_text()
        assert text.count("tpu_custom_call") >= 1 + tiles, (slab, heads)
    plan = {"staged blocks": 2 * la._STAGE * 8 * 128 * 4,
            "picked rows": G * (la._ROWS + la._PICKS) * 8 * 128 * 4,
            "queries": G * 128 * 640 * 2,
            "m and l": 2 * G * 128 * 128 * 4, "acc": G * 128 * 512 * 4}
    print("latent_attention_staged VMEM plan (MiB):",
          {k: v / 2 ** 20 for k, v in plan.items()},
          f"total {sum(plan.values()) / 2 ** 20:.2f} of "
          f"{la._VMEM_LIMIT / 2 ** 20:.0f}")
    assert sum(plan.values()) < la._VMEM_LIMIT


def test_mla_sparse_serve_step_compiles_for_v5e_and_fits_the_chip(devices):
    """The latent-attention step with a learned indexer at the benchmark
    cell's shapes — published widths, 5 layers (2 full with the indexer, 3
    sliding; 1 dense, 4 expert), 32 of 256 experts, 19,008 ids, 16 rows,
    chunks of 512, 8,704 pages of 64 and 288 window pages: Mosaic accepts
    the latent kernel in every layer (5 calls, and the staged call beside
    the per-row one in each of the 2 full layers) and the grouped expert
    product (two calls in each of the 4 expert layers); the three pools
    are donated and aliased, and the plan fits the chip: 8.17 GB of
    weights and 5.30 GB of pools.  The scopes the cell's readers read name
    the instructions."""
    compiled = tpu_aot.lower_mla_sparse_serve_step(devices).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count('custom_call_target="tpu_custom_call"') \
        == 5 + 2 + 2 * 4
    pools = (2 * 8704 * 64 * 8 * 128 * 4 + 2 * 8704 * 64 * 128 * 2
             + 3 * 288 * 64 * 16 * 128 * 4)
    assert mem.alias_size_in_bytes == pools
    assert mem.temp_size_in_bytes < 0.5e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 2 ** 34
    cw, table, calls = _table(compiled)
    scopes = lambda p: cw.named_scopes(p)
    assert len([p for p in calls.values()
                if "latent_attn" in scopes(p)]) == 5 + 2
    assert len([p for p in calls.values() if "experts" in scopes(p)]) == 8
    for scope in ("indexer", "index_topk", "latent_write", "latent_attn",
                  "full_attn", "window_attn", "router", "mlp", "lm_head",
                  "embed"):
        assert any(scope in scopes(p) for p in table.values()), scope
