"""Ask the TPU's compiler, without the TPU: AOT compiles of the main path
for a DESCRIBED v5e (`/opt/skills/guides/on-chip-measurement`, section 2).

Interpret mode cannot show what Mosaic refuses (tile alignment, VMEM) or
what does not fit the chip; the installed libtpu compiles for a topology
that is described and not attached, so these guard every PR at no chip
time.  Shapes are the full width of the repo's 8-layer / 512-wide / 8k
LM (vocab 32000, bf16) that ``chip_smoke.py`` runs.  A compile that
passes is not a chip run.

Code that asks ``jax.default_backend()`` sees the CPU here and would take
its interpret branch, so the lowering runs with that one function patched
(the program grows no option for it).
"""

import base64
import contextlib
import dataclasses
import hashlib
import math
import os
import re
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from tpudist import obs
from tpudist.models import (MLAConfig, MoEConfig, ServeLoop,
                            TransformerConfig, TransformerLM, YarnScaling)
from tpudist.models.serving import hlo_scopes
from tpudist.ops.flash_attention import _flash_forward, flash_attention
from tpudist.ops.flash_decode import (flash_decode, paged_flash_decode,
                                      paged_mla_decode)

VOCAB, LAYERS, EMBED, SEQ = 32000, 8, 512, 8192
SLOTS, STEPS, CHUNK, BLOCK = 4, 32, 512, 128
# (num_heads, num_kv_heads): the head_dim-128 serve/train layout and the
# 8q/2kv head_dim-64 layout the tp=2 phase of chip_smoke.py needs
LAYOUTS = [(4, 1), (8, 2)]


def _cfg(heads: int, kv_heads: int) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=VOCAB, num_layers=LAYERS, num_heads=heads,
        num_kv_heads=kv_heads, embed_dim=EMBED, max_seq_len=SEQ,
        compute_dtype=jnp.bfloat16)


@pytest.fixture(scope="module")
def v5e():
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / unknown topology
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _cache_off():
    """A described-device executable can be written to the persistent
    cache but not read back without a chip (it warns and recompiles), so
    the cache is off around these compiles."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def _quiet_cache():
    with _cache_off():
        yield


def _on(sharding, tree):
    """Shapes of ``tree`` (arrays or ShapeDtypeStructs) on the described
    device — there is no device to hold an array."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **static) -> str:
    """Compiled HLO text of ``fn`` (a function, or an existing ``jax.jit``)
    for the described device."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        return jitted.lower(*args, **static).compile().as_text()


def _kernel_calls(hlo: str) -> int:
    return hlo.count('custom_call_target="tpu_custom_call"')


def _entry_products(hlo: str, shape: str) -> int:
    """How many instructions of the entry computation give ``shape`` out of
    a product: fusions whose computation holds a ``convolution``, which is
    how the TPU compiler writes a ``dot``."""
    bodies = dict(re.findall(r"^%([\w.\-]+) \([^\n]*\{\n(.*?)^\}", hlo,
                             re.M | re.S))
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", hlo, re.M | re.S).group(1)
    fusions = re.findall(
        rf"^\s*%[\w.\-]+ = {re.escape(shape)}\S* fusion\(.*calls=%([\w.\-]+)",
        entry, re.M)
    return sum(" convolution(" in bodies[calls] for calls in fusions)


def _custom_call(hlo: str, name: str) -> dict:
    """The one custom call whose instruction is named ``name``, parsed as
    the benchmark parses a trace's event (a device event is named by its
    whole instruction)."""
    from benchmarks.trace.reduce import parse_op

    (line,) = [l for l in hlo.splitlines()
               if re.match(rf"\s*%{name}[.\d]* = ", l)]
    return parse_op(line.strip())


# (lanes, heads, kv heads, head_dim, page-table entries, pool blocks): the
# two layouts at the smoke's size, and the benchmark's serving cell
# (starcoderbase-3b: 24 lanes, 22 heads, 1 K/V head of 128, 64 entries,
# 1024 blocks) so that a VMEM or lowering refusal shows here
PAGED_SHAPES = {
    "4q1kv": (SLOTS, 4, 1, EMBED // 4, SEQ // BLOCK, SLOTS * (SEQ // BLOCK)),
    "8q2kv": (SLOTS, 8, 2, EMBED // 8, SEQ // BLOCK, SLOTS * (SEQ // BLOCK)),
    "cell_sc3b": (24, 22, 1, 128, 64, 1024),
}


@pytest.mark.parametrize("side", [True, False], ids=["side", "noside"])
@pytest.mark.parametrize("shape", PAGED_SHAPES)
def test_paged_flash_decode(v5e, shape, side):
    lanes, heads, kv_heads, d, entries, n_blocks = PAGED_SHAPES[shape]
    flat = kv_heads * d
    q = _sds(v5e, (lanes, 1, heads, d))
    pool = _sds(v5e, (n_blocks, BLOCK, flat))
    table = _sds(v5e, (lanes, entries), jnp.int32)
    lens = _sds(v5e, (lanes,), jnp.int32)
    if side:
        buf = _sds(v5e, (lanes, STEPS, flat))
        hlo = _compile(
            lambda q, k, v, t, n, sk, sv, sl: paged_flash_decode(
                q, k, v, t, n, packed_kv_heads=kv_heads, side_k=sk,
                side_v=sv, side_len=sl),
            q, pool, pool, table, lens, buf, buf, _sds(v5e, (), jnp.int32))
    else:
        hlo = _compile(
            lambda q, k, v, t, n: paged_flash_decode(
                q, k, v, t, n, packed_kv_heads=kv_heads),
            q, pool, pool, table, lens)
    assert _kernel_calls(hlo) == 1
    # what the roofline's reader tells the kernel by: a Pallas call of
    # meta, q, the two pools (and the two side buffers) with ONE output
    from benchmarks.roofline import paged_decode

    op = _custom_call(hlo, "paged_flash_decode")
    assert op["pallas"] and op["operands"] == (6 if side else 4)
    assert len(op["outputs"]) == 1 and paged_decode.is_kernel(op) == side


# The DeepSeek-V3 cell (dsv3_reason_batch): 128 lanes, 128 heads, a latent
# row of 640 (512 latent + 64 rotary + 64 zeros), block 128, 64 table
# entries, side 32; 16 held experts of 7168 x 2048.
MLA_LANES, MLA_HEADS, MLA_ROW, MLA_LATENT, MLA_BLOCKS = 128, 128, 640, 512, 2048


@pytest.mark.parametrize("side", [True, False], ids=["side", "noside"])
def test_paged_mla_decode_at_the_cells_shapes(v5e, side):
    q = _sds(v5e, (MLA_LANES, MLA_HEADS, MLA_ROW))
    pool = _sds(v5e, (MLA_BLOCKS, BLOCK, MLA_ROW))
    table = _sds(v5e, (MLA_LANES, SEQ // BLOCK), jnp.int32)
    lens = _sds(v5e, (MLA_LANES,), jnp.int32)
    kw = dict(d_v=MLA_LATENT, scale=0.1352)
    if side:
        hlo = _compile(
            lambda q, p, t, n, s, sl: paged_mla_decode(
                q, p, t, n, side=s, side_len=sl, **kw),
            q, pool, table, lens, _sds(v5e, (MLA_LANES, STEPS, MLA_ROW)),
            _sds(v5e, (), jnp.int32))
    else:
        hlo = _compile(lambda q, p, t, n: paged_mla_decode(q, p, t, n, **kw),
                       q, pool, table, lens)
    assert _kernel_calls(hlo) == 1
    # the benchmark's readers find this kernel BY NAME; by operands it is
    # meta, q, ONE pool (and one side buffer), so the shape test that tells
    # paged_flash_decode (six operands) never counts it
    from benchmarks.layer_metrics.mla_decode_us_per_call import KERNEL
    from benchmarks.roofline import paged_decode

    (line,) = [l.strip() for l in hlo.splitlines()
               if re.match(r"\s*(ROOT )?%paged_mla_decode[.\d]* = ", l)]
    assert KERNEL.match(line.removeprefix("ROOT "))
    op = _custom_call(hlo.replace("ROOT %", "%"), "paged_mla_decode")
    assert op["pallas"] and op["operands"] == (4 if side else 3)
    assert len(op["outputs"]) == 1 and not paged_decode.is_kernel(op)


@pytest.mark.parametrize("tokens", [MLA_LANES, CHUNK],
                         ids=["decode_step", "prefill_chunk"])
def test_grouped_expert_product_at_the_cells_shapes(v5e, tokens):
    """16 held experts of 7168 x 2048, 8 choices a token over 256: a decode
    step of 128 lanes (row block 16: about 64 live rows) and a prefill
    chunk of 512."""
    from benchmarks.layer_metrics.moe_experts_roofline import KERNELS
    from tpudist.ops.moe_dispatch import grouped_gated_mlp, row_block

    d, f, held, top_k, experts = 7168, 2048, 16, 8, 256
    assert row_block(MLA_LANES * top_k, experts) == 16
    hlo = _compile(
        lambda x, wg, wu, wd, i, w: grouped_gated_mlp(
            x, wg, wu, wd, i, w, num_experts=experts),
        _sds(v5e, (tokens, d)), _sds(v5e, (held, d, f)),
        _sds(v5e, (held, d, f)), _sds(v5e, (held, f, d)),
        _sds(v5e, (tokens, top_k), jnp.int32),
        _sds(v5e, (tokens, top_k), jnp.float32))
    assert _kernel_calls(hlo) == 2
    for name in ("moe_experts_gate_up", "moe_experts_down"):
        op = _custom_call(hlo, name)
        assert op["pallas"] and len(op["outputs"]) == 1
    assert sum(bool(KERNELS.match(l.strip())) for l in hlo.splitlines()) == 2


def test_flash_forward_with_a_narrower_value(v5e):
    """Expanded latent attention in a prefill chunk: q/k 192 wide, v 128."""
    hlo = _compile(
        lambda q, k, v, off: _flash_forward(
            q, k, v, True, CHUNK, 1024, False, q_offset=off,
            scale=0.1352)[0],
        _sds(v5e, (1, CHUNK, MLA_HEADS, 192)),
        _sds(v5e, (1, 1024, MLA_HEADS, 192)),
        _sds(v5e, (1, 1024, MLA_HEADS, 128)), _sds(v5e, (), jnp.int32))
    assert _kernel_calls(hlo) == 1
    assert f"bf16[{MLA_HEADS},{CHUNK},128]" in hlo


@pytest.mark.parametrize("heads,kv_heads", LAYOUTS)
def test_per_row_flash_decode(v5e, heads, kv_heads):
    d = EMBED // heads
    cache = _sds(v5e, (SLOTS, SEQ, kv_heads * d))
    hlo = _compile(
        lambda q, k, v, n: flash_decode(q, k, v, n,
                                        packed_kv_heads=kv_heads),
        _sds(v5e, (SLOTS, 1, heads, d)), cache, cache,
        _sds(v5e, (SLOTS,), jnp.int32))
    assert _kernel_calls(hlo) == 1


@pytest.mark.parametrize("heads,kv_heads", LAYOUTS)
def test_flash_forward_at_query_offset(v5e, heads, kv_heads):
    """The prefill chunk: 512 queries at a dynamic offset into the 8k
    cache, blocks as ``CausalSelfAttention._prefill_attend`` picks them."""
    d = EMBED // heads
    kv = _sds(v5e, (1, SEQ, kv_heads, d))
    hlo = _compile(
        lambda q, k, v, off: _flash_forward(
            q, k, v, True, CHUNK, 1024, False, q_offset=off)[0],
        _sds(v5e, (1, CHUNK, heads, d)), kv, kv, _sds(v5e, (), jnp.int32))
    assert _kernel_calls(hlo) == 1


@pytest.mark.parametrize("heads,kv_heads", LAYOUTS)
def test_flash_attention_forward_backward_8k(v5e, heads, kv_heads):
    d = EMBED // heads
    q = _sds(v5e, (1, SEQ, heads, d))
    kv = _sds(v5e, (1, SEQ, kv_heads, d))

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v).astype(jnp.float32))

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert _kernel_calls(hlo) >= 3  # forward, dQ, dK/dV


def _abstract_params(model):
    """Parameter shapes only (a loop or a step only stores them)."""
    return jax.eval_shape(model.init, jax.random.key(0),
                          jnp.ones((1, 8), jnp.int32))["params"]


def _segment_args(v5e, loop):
    return _on(v5e, (loop.params, loop.cache, loop._tok, loop._active,
                     loop._remaining, loop._first, loop._key,
                     jnp.int32(STEPS), jnp.bool_(False)))


@pytest.fixture(scope="module")
def serve_loop():
    """The serve phase of chip_smoke.py, built as ``ServeLoop.__init__``
    builds it; params stay abstract."""
    cfg = _cfg(4, 1)
    params = _abstract_params(TransformerLM(cfg))
    return ServeLoop(cfg, params, num_slots=SLOTS, steps_per_sync=STEPS,
                     decode_attention="flash", prefill_chunk=CHUNK,
                     cache_layout="paged", kv_block_size=BLOCK)


def test_serve_segment_program(v5e, serve_loop):
    loop = serve_loop
    args = _segment_args(v5e, loop)
    assert _kernel_calls(_compile(loop._segment, *args)) == LAYERS


def test_serve_prefill_chunk_program(v5e, serve_loop):
    loop = serve_loop
    args = _on(v5e, (loop.params, loop._blank1,
                     jnp.zeros((1, CHUNK), jnp.int32), jnp.int32(0)))
    hlo = _compile(loop._prefill_chunk, *args, chunk=CHUNK)
    assert _kernel_calls(hlo) == LAYERS


# Every Pallas call of the hot path carries a stable ``name=``: it becomes
# the op's ``<name>/pallas_call`` scope, which the chip's compiler turns
# into the instruction's name (``%paged_flash_decode.7``) and a profiler
# trace shows.  Lowering is enough to see it; nothing is compiled here.

def _kernel_scopes(fn, *args, **static) -> set[str]:
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = jitted.lower(*args, **static).as_text(debug_info=True)
    # a call under its own ``jit`` (paged_flash_decode) is located from
    # the callee: its scope then starts the location, without a slash
    return set(re.findall(r"\b(\w+)/pallas_call", text))


def test_segment_names_its_kernel(v5e, serve_loop):
    loop = serve_loop
    args = _segment_args(v5e, loop)
    assert _kernel_scopes(loop._segment, *args) == {"paged_flash_decode"}


def test_prefill_chunk_names_its_kernel(v5e, serve_loop):
    loop = serve_loop
    args = _on(v5e, (loop.params, loop._blank1,
                     jnp.zeros((1, CHUNK), jnp.int32), jnp.int32(0)))
    assert _kernel_scopes(loop._prefill_chunk, *args,
                          chunk=CHUNK) == {"flash_fwd"}


def test_latent_expert_model_names_its_kernels(v5e):
    """A model with latent attention and expert layers (DeepSeek-V3's
    block at a small size): the segment's kernels and the prefill chunk's,
    by the names a trace shows."""
    from tpudist.models import MLAConfig, MoEConfig, YarnScaling

    moe = MoEConfig(num_experts=16, top_k=4, experts="gated_silu", d_ff=128,
                    scoring="sigmoid", n_group=4, topk_group=2,
                    routed_scale=2.5, correction_bias=True, n_shared=1,
                    held=(0, 4))
    cfg = TransformerConfig(
        vocab_size=1024, num_layers=2, num_heads=8, embed_dim=512,
        max_seq_len=2048, compute_dtype=jnp.bfloat16, norm="rmsnorm",
        positions="rotary", rope_scaling=YarnScaling(mscale_all_dim=1.0),
        mlp="gated_silu", mlp_dim=1024,
        mla=MLAConfig(384, 512, 128, 64, 128), moe=moe, first_k_dense=1)
    loop = ServeLoop(cfg, _abstract_params(TransformerLM(cfg)),
                     num_slots=SLOTS, steps_per_sync=STEPS,
                     decode_attention="flash", prefill_chunk=CHUNK,
                     cache_layout="paged", kv_block_size=BLOCK)
    assert _kernel_scopes(loop._segment, *_segment_args(v5e, loop)) == {
        "paged_mla_decode", "moe_experts_gate_up", "moe_experts_down"}
    args = _on(v5e, (loop.params, loop._blank1,
                     jnp.zeros((1, CHUNK), jnp.int32), jnp.int32(0)))
    assert _kernel_scopes(loop._prefill_chunk, *args, chunk=CHUNK) == {
        "flash_fwd", "moe_experts_gate_up", "moe_experts_down"}
    assert _kernel_calls(_compile(loop._segment,
                                  *_segment_args(v5e, loop))) == 4


# The Mellum 2 cell (mellum2_agent_mixed): 40 lanes, 32 query heads on 4
# K/V heads of 128, block 128, 64 table entries, side 16, a window of 1024
# in a pool of its own (400 blocks); 16 held experts of 2304 x 896 of 64.
WIN_LANES, WIN_HEADS, WIN_KV, WIN_D, WIN_W, WIN_STEPS = 40, 32, 4, 128, 1024, 16


@pytest.mark.parametrize("kv_heads", [WIN_KV, 2 * WIN_KV],
                         ids=["cell", "8_kv_heads_two_rows_a_lane"])
def test_paged_window_decode_at_the_cells_shapes(v5e, kv_heads):
    """The windowed walk for the chip: its own name, the plain kernel's
    six operands and one output, so that readers by name tell the two
    apart and no reader by shape counts it twice.  A lane's four K/V heads
    share a grid row (4 MiB of tile slots: ``_TILE_SLOT_BYTES``); eight are
    over that budget and take two rows of four: what VMEM refuses of
    either shows here and not on the chip."""
    from tpudist.ops.flash_decode import paged_grid_rows

    assert paged_grid_rows(WIN_LANES, kv_heads, WIN_D, BLOCK, SEQ // BLOCK
                           ) == WIN_LANES * kv_heads // WIN_KV
    flat = kv_heads * WIN_D
    q = _sds(v5e, (WIN_LANES, 1, WIN_HEADS * kv_heads // WIN_KV, WIN_D))
    pool = _sds(v5e, (400, BLOCK, flat))
    table = _sds(v5e, (WIN_LANES, SEQ // BLOCK), jnp.int32)
    lens = _sds(v5e, (WIN_LANES,), jnp.int32)
    buf = _sds(v5e, (WIN_LANES, WIN_STEPS, flat))

    def call(window):
        return _compile(
            lambda q, k, v, t, n, sk, sv, sl: paged_flash_decode(
                q, k, v, t, n, packed_kv_heads=kv_heads, side_k=sk,
                side_v=sv, side_len=sl, window=window),
            q, pool, pool, table, lens, buf, buf, _sds(v5e, (), jnp.int32))

    from benchmarks.layer_metrics.paged_decode_us_per_call import KERNEL
    from benchmarks.layer_metrics.window_decode_us_per_call import (
        KERNEL as WINDOW_KERNEL)

    for window, name, mine, other in (
            (WIN_W, "paged_window_decode", WINDOW_KERNEL, KERNEL),
            (None, "paged_flash_decode", KERNEL, WINDOW_KERNEL)):
        hlo = call(window).replace("ROOT %", "%")
        assert _kernel_calls(hlo) == 1
        op = _custom_call(hlo, name)
        assert op["pallas"] and op["operands"] == 6
        assert len(op["outputs"]) == 1
        (line,) = [l.strip() for l in hlo.splitlines()
                   if re.match(rf"\s*%{name}[.\d]* = ", l)]
        assert mine.match(line) and not other.match(line)


def test_window_none_lowers_as_before_the_window(v5e):
    """``window=None`` is the program of PR 30, instruction for instruction:
    the kernel's lowered text does not depend on the argument being passed,
    and differs from the windowed one."""
    lanes, heads, kv_heads, d, entries, n_blocks = PAGED_SHAPES["cell_sc3b"]
    flat = kv_heads * d
    args = (_sds(v5e, (lanes, 1, heads, d)), _sds(v5e, (n_blocks, BLOCK, flat)),
            _sds(v5e, (n_blocks, BLOCK, flat)),
            _sds(v5e, (lanes, entries), jnp.int32),
            _sds(v5e, (lanes,), jnp.int32), _sds(v5e, (lanes, STEPS, flat)),
            _sds(v5e, (lanes, STEPS, flat)), _sds(v5e, (), jnp.int32))

    def text(**kw):
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            return jax.jit(
                lambda q, k, v, t, n, sk, sv, sl: paged_flash_decode(
                    q, k, v, t, n, packed_kv_heads=kv_heads, side_k=sk,
                    side_v=sv, side_len=sl, **kw)).lower(*args).as_text()

    plain = text()
    assert text(window=None) == plain
    assert text(window=1024) != plain


def test_rolling_prefill_flash_forward_at_the_cells_shapes(v5e):
    """A windowed layer's prefill chunk: 512 queries at a dynamic offset
    against the rolling buffer of 1024 + 512 rows at a dynamic base."""
    kv = _sds(v5e, (1, WIN_W + CHUNK, WIN_KV, WIN_D))
    hlo = _compile(
        lambda q, k, v, off, base: _flash_forward(
            q, k, v, True, CHUNK, 512, False, q_offset=off, k_offset=base,
            window=WIN_W)[0],
        _sds(v5e, (1, CHUNK, WIN_HEADS, WIN_D)), kv, kv,
        _sds(v5e, (), jnp.int32), _sds(v5e, (), jnp.int32))
    assert _kernel_calls(hlo) == 1


@pytest.mark.parametrize("tokens", [WIN_LANES, CHUNK],
                         ids=["decode_step", "prefill_chunk"])
def test_grouped_expert_product_at_2304_by_896(v5e, tokens):
    """16 held experts of 2304 x 896, 8 choices a token over 64: a decode
    step of 40 lanes (row block 16) and a prefill chunk of 512 (128)."""
    from tpudist.ops.moe_dispatch import (_tiles, grouped_gated_mlp,
                                          row_block)

    d, f, held, top_k, experts = 2304, 896, 16, 8, 64
    assert row_block(WIN_LANES * top_k, experts) == 16
    assert row_block(CHUNK * top_k, experts) == 128
    # whole rows of an expert's matrix, the contraction in two halves
    assert _tiles(d, f, 2, 2 << 20) == (1152, 896)
    assert _tiles(f, d, 2, 4 << 20) == (896, 2304)
    hlo = _compile(
        lambda x, wg, wu, wd, i, w: grouped_gated_mlp(
            x, wg, wu, wd, i, w, num_experts=experts),
        _sds(v5e, (tokens, d)), _sds(v5e, (held, d, f)),
        _sds(v5e, (held, d, f)), _sds(v5e, (held, f, d)),
        _sds(v5e, (tokens, top_k), jnp.int32),
        _sds(v5e, (tokens, top_k), jnp.float32))
    assert _kernel_calls(hlo) == 2


def test_mixed_window_model_names_its_kernels(v5e):
    """A model with sliding-window and full layers, grouped queries at a
    stated head width and expert layers (Mellum 2's block at a small
    size): the segment's kernels and the prefill chunk's, by the names a
    trace shows, and the finish program for both block groups."""
    from tpudist.models import MoEConfig, YarnScaling

    moe = MoEConfig(num_experts=16, top_k=4, experts="gated_silu", d_ff=128,
                    scoring="softmax", held=(0, 4))
    cfg = TransformerConfig(
        vocab_size=1024, num_layers=4, num_heads=8, num_kv_heads=2,
        head_size=128, embed_dim=512, max_seq_len=4096,
        compute_dtype=jnp.bfloat16, norm="rmsnorm", positions="rotary",
        rope_theta=500000.0,
        rope_scaling=YarnScaling(16.0, 8192, 32.0, 1.0, 1.0, 0.0),
        window_rope_scaling=None, layer_windows=(1024, 1024, 1024, None),
        mlp="gated_silu", mlp_dim=128, moe=moe)
    loop = ServeLoop(cfg, _abstract_params(TransformerLM(cfg)),
                     num_slots=SLOTS, steps_per_sync=WIN_STEPS,
                     decode_attention="flash", prefill_chunk=CHUNK,
                     cache_layout="paged", kv_block_size=BLOCK)
    assert loop.kv_window_blocks == SLOTS * 10
    seg = _on(v5e, (loop.params, loop.cache, loop._tok, loop._active,
                    loop._remaining, loop._first, loop._key,
                    jnp.int32(WIN_STEPS), jnp.bool_(False)))
    assert _kernel_scopes(loop._segment, *seg) == {
        "paged_window_decode", "paged_flash_decode", "moe_experts_gate_up",
        "moe_experts_down"}
    assert _kernel_calls(_compile(loop._segment, *seg)) == 4 + 2 * 4
    # a windowed layer's batch-1 cache is the window and a chunk
    assert loop._blank1["block0"]["attn"]["cached_key"].shape == (
        1, 1024 + CHUNK, 256)
    assert loop._blank1["block3"]["attn"]["cached_key"].shape == (
        1, 4096, 256)
    chunk = _on(v5e, (loop.params, loop._blank1,
                      jnp.zeros((1, CHUNK), jnp.int32), jnp.int32(0),
                      jnp.int32(0)))
    assert _kernel_scopes(loop._prefill_chunk, *chunk, chunk=CHUNK) == {
        "flash_fwd", "moe_experts_gate_up", "moe_experts_down"}
    assert _kernel_calls(_compile(loop._prefill_chunk, *chunk,
                                  chunk=CHUNK)) == 4 + 2 * 4
    pages = (jnp.zeros((32,), jnp.int32), jnp.zeros((32,), jnp.int32))
    finish = _on(v5e, (loop.cache, loop._tok, loop._active, loop._remaining,
                       loop._first, loop._blank1,
                       jnp.zeros((1, 1, 1024), jnp.float32), jnp.int32(0),
                       jnp.int32(5), jnp.int32(0), jnp.int32(5), pages,
                       jnp.int32(0), loop._key))
    _compile(loop._admit_finish, *finish)


# The Command A+ cell (cmdaplus_rag_mixed) as its own files build it: the
# parallel block, 128 query heads on 8 K/V heads of 128 (TWO grid rows of
# four heads a lane in both paged walks), a window of 4096 in a group of 34
# blocks a lane, 16 held experts of 4096 x 4096 of 128, four shared, a head
# tied to 32768 rows.

def _kernels_by_name(hlo: str) -> dict:
    names = re.findall(
        r'^\s*(?:ROOT )?%([a-z_]+)[.\d]* = [^\n]*'
        r'custom_call_target="tpu_custom_call"', hlo, re.M)
    return {n: names.count(n) for n in set(names)}


def test_command_a_cell_programs_at_full_size(v5e):
    """Segment, chunk and finish of the cell at the sizes its configuration
    file states, for the described chip: what the chip's compiler refuses
    of 8 K/V heads, a 4096-row window or 4096-wide experts shows here; the
    kernels by the names a trace shows."""
    from benchmarks.harness import common, serve_command_a as runner
    from tpudist.ops.flash_decode import paged_grid_rows

    cell = common.load_cell("cmdaplus_rag_mixed")
    config = cell["config"]
    dims = runner.model_dims(config)
    params = jax.eval_shape(
        lambda: runner.make_params(0, dims, jnp.bfloat16, 1.0))
    assert "lm_head" not in params
    loop = runner.build_loop(config, dims, params, tiny=False)
    lanes = config["program"]["options"]["num_slots"]
    assert loop.kv_window_blocks == lanes * 34
    assert loop._grid_rows == 2 * lanes == paged_grid_rows(
        lanes, 8, 128, 128, loop.pool.max_blocks_per_slot)
    assert loop._row_heads == 4
    chunk = config["program"]["options"]["prefill_chunk"]
    assert loop._blank1["block0"]["attn"]["cached_key"].shape == (
        1, 4096 + chunk, 1024)
    assert loop._blank1["block3"]["attn"]["cached_key"].shape == (
        1, 18432, 1024)
    got = {name: _kernels_by_name(_compile(jitted, *_on(v5e, args),
                                           **static))
           for name, (jitted, args, static)
           in loop.serve_programs().items()}
    experts = {"moe_experts_gate_up": 4, "moe_experts_down": 4}
    assert got == {
        "_segment_impl": {"paged_window_decode": 3, "paged_flash_decode": 1,
                          **experts},
        "_prefill_chunk_impl": {"flash_fwd": 4, **experts},
        "_admit_finish_impl": {}}


# The Olmo-Hybrid cell (olmoh7b_doc_mixed): 16 lanes, 30 linear-attention
# heads of a 96 x 192 float32 state each (kept [96, 30 x 192] a lane), prefill
# chunks of 512 tokens; the full layers 30 query heads on 30 K/V heads of 128
# over 72 table entries of 128 rows.
LIN_LANES, LIN_HEADS, LIN_DK, LIN_DV, LIN_CHUNK = 16, 30, 96, 192, 512


def test_delta_step_at_the_cells_shapes(v5e):
    """The decode kernel of the gated delta rule: every lane's state read
    once and written once IN PLACE (the state argument is donated and the
    second output aliases it), ten heads a grid step, under its name."""
    from tpudist.ops.delta_rule import gated_delta_step, step_heads

    assert step_heads(LIN_HEADS, LIN_DK, LIN_DV) == (10, 2)
    f32 = jnp.float32
    vec = lambda *shape: _sds(v5e, (LIN_LANES, LIN_HEADS, *shape), f32)  # noqa
    state = _sds(v5e, (LIN_LANES, LIN_DK, LIN_HEADS * LIN_DV), f32)
    step = jax.jit(
        lambda q, k, v, g, b, s, ok: gated_delta_step(q, k, v, g, b, s, ok),
        donate_argnums=(5,))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        compiled = step.lower(
            vec(LIN_DK), vec(LIN_DK), vec(LIN_DV), vec(), vec(), state,
            _sds(v5e, (LIN_LANES,), jnp.bool_)).compile()
    hlo = compiled.as_text()
    assert _kernel_calls(hlo) == 1
    op = _custom_call(hlo, "delta_step")
    assert op["operands"] == 6 and len(op["outputs"]) == 2
    # no second copy of the 35 MB of states: the output is the argument
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= LIN_LANES * LIN_DK * LIN_HEADS \
        * LIN_DV * 4
    assert memory.temp_size_in_bytes < 4 << 20


def test_delta_chunk_at_the_cells_shapes(v5e):
    """The chunk form at a prefill chunk's width: XLA products, no kernel,
    a loop over the sub-blocks."""
    from tpudist.ops.delta_rule import gated_delta_chunk

    f32 = jnp.float32
    tok = lambda *shape: _sds(v5e, (1, LIN_CHUNK, LIN_HEADS, *shape), f32)  # noqa
    hlo = _compile(
        gated_delta_chunk, tok(LIN_DK), tok(LIN_DK), tok(LIN_DV), tok(),
        tok(), _sds(v5e, (1, LIN_HEADS, LIN_DK, LIN_DV), f32),
        _sds(v5e, (1, LIN_CHUNK), jnp.bool_))
    assert _kernel_calls(hlo) == 0
    assert re.search(r"^\s*%while[.\d]* = ", hlo, re.M)


def test_mixed_kind_model_names_its_kernels(v5e):
    """Linear-attention layers beside full attention at many K/V heads (the
    Olmo-Hybrid block at a small size: three linear layers then a full one,
    the norm after the sublayer, no positions): the segment's kernels and
    the prefill chunk's by the names a trace shows, each under its routine,
    and the finish program, which copies the state by slot."""
    from tpudist.models.transformer import LinearAttentionConfig

    cfg = TransformerConfig(
        vocab_size=1024, num_layers=4, num_heads=8, head_size=128,
        embed_dim=512, max_seq_len=2048, compute_dtype=jnp.bfloat16,
        norm="rmsnorm", norm_order="post", positions="none",
        mlp="gated_silu", mlp_dim=1024, qk_norm="whole",
        layer_kinds=("linear", "linear", "linear", "full"),
        linear=LinearAttentionConfig(num_heads=4, key_dim=96,
                                     value_dim=192))
    loop = ServeLoop(cfg, _abstract_params(TransformerLM(cfg)),
                     num_slots=SLOTS, steps_per_sync=SCOPE_STEPS,
                     decode_attention="flash", prefill_chunk=CHUNK,
                     cache_layout="paged", kv_block_size=BLOCK,
                     max_prefill_lanes=2)
    programs = {
        name: _compile(jitted, *_on(v5e, args), **static)
        for name, (jitted, args, static) in loop.serve_programs().items()}
    seg, chunk = programs["_segment_impl"], programs["_prefill_chunk_impl"]
    assert _kernel_calls(seg) == 3 + 1 and _kernel_calls(chunk) == 1
    found = {prog: set(hlo_scopes(text).values())
             for prog, text in programs.items()}
    assert {"linear_attn", "delta_step", "attn/core", "attn/cache",
            "mlp/dense", "head"} <= found["_segment_impl"]
    assert {"linear_attn", "delta_chunk", "attn/core"} <= found[
        "_prefill_chunk_impl"]
    assert "delta_chunk" not in found["_segment_impl"]
    assert "delta_step" not in found["_prefill_chunk_impl"]
    for name, routine in (("delta_step", "delta_step"),
                          ("paged_flash_decode", "attn/core")):
        scoped = [scope for inst, scope in hlo_scopes(seg).items()
                  if re.match(rf"{name}[.\d]*$", inst)]
        assert scoped and set(scoped) == {routine}, name
    # the state rides in the slot cache beside the pages, by slot
    lin = loop.cache["block0"]["linear_attn"]
    assert lin["state"].shape == (SLOTS, 96, 4 * 192)
    assert lin["conv"].shape == (SLOTS, 3 * 4 * (2 * 96 + 192))
    assert "page_table" in loop.cache["block3"]["attn"]


# The Keye-VL 2.0 cell (keye2_longdoc_mixed): 16 lanes, 256 table entries of
# 128 rows, a pool of 2900 blocks, side 16; 32 query heads on 4 K/V heads of
# 128; an indexer of 16 heads of 64 (a key stored 128 wide) that keeps 2048
# rows; prefill chunks of 2048 queries over a batch-1 cache of 32768 rows.
IDX_LANES, IDX_HEADS, IDX_WIDTH, IDX_TOPK = 16, 16, 128, 2048
IDX_ENTRIES, IDX_BLOCKS, IDX_CHUNK, IDX_SIDE = 256, 2900, 2048, 16
IDX_Q, IDX_KV, IDX_D = 32, 4, 128


def _named_call(hlo: str, name: str, pattern) -> dict:
    (line,) = [l.strip() for l in hlo.splitlines()
               if re.match(rf"\s*(ROOT )?%{name}[.\d]* = ", l)]
    assert pattern.match(line.removeprefix("ROOT "))
    return _custom_call(hlo.replace("ROOT %", "%"), name)


def _big_f32_moves(hlo: str, numbers: int) -> list:
    """Instructions that only MOVE a float32 array of ``numbers`` numbers:
    a ``copy`` or a ``transpose`` (a ``bitcast`` moves nothing)."""
    moves = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = f32\[([\d,]+)\]\S* "
                     r"(copy|transpose)\(", line)
        if m and math.prod(map(int, m.group(1).split(","))) == numbers:
            moves.append(line.strip())
    return moves


def _chunk_scores(v5e, heads):
    """Arguments of a chunk's ``paged_index_scores`` at the cell's sizes
    with ``heads`` index heads, and the queries a grid row takes."""
    from tpudist.ops.flash_decode import index_queries_per_row

    tq = index_queries_per_row(IDX_CHUNK, heads, IDX_ENTRIES * BLOCK)
    return tq, (_sds(v5e, (IDX_CHUNK, heads, IDX_WIDTH)),
                _sds(v5e, (IDX_CHUNK, heads), jnp.float32),
                _sds(v5e, (IDX_ENTRIES, BLOCK, IDX_WIDTH)),
                _sds(v5e, (1, IDX_ENTRIES), jnp.int32),
                _sds(v5e, (IDX_CHUNK // tq,), jnp.int32))


@pytest.mark.parametrize("path", ["decode_step", "prefill_chunk"])
def test_paged_index_scores_at_the_cells_shapes(v5e, path):
    """A decode step: a grid row a lane, each its own pages.  A prefill
    chunk: 16 queries a grid row over ONE table row (the batch-1 cache's
    32768 rows seen as pages).  Either way the kernel writes a grid row's
    queries by ALL the row's columns."""
    from benchmarks.layer_metrics import _index_spans
    from tpudist.ops.flash_decode import paged_index_scores

    if path == "decode_step":
        tq, args = 1, (_sds(v5e, (IDX_LANES, IDX_HEADS, IDX_WIDTH)),
                       _sds(v5e, (IDX_LANES, IDX_HEADS), jnp.float32),
                       _sds(v5e, (IDX_BLOCKS, BLOCK, IDX_WIDTH)),
                       _sds(v5e, (IDX_LANES, IDX_ENTRIES), jnp.int32),
                       _sds(v5e, (IDX_LANES,), jnp.int32))
    else:
        tq, args = _chunk_scores(v5e, IDX_HEADS)
        assert tq == 16
    hlo = _compile(paged_index_scores, *args)
    assert _kernel_calls(hlo) == 1
    op = _named_call(hlo, "paged_index_scores", _index_spans.SCORES)
    assert op["pallas"] and op["operands"] == 4      # meta, q, w, pool
    assert op["outputs"] == (
        f"f32[{args[-1].shape[0]},{tq},{IDX_ENTRIES * BLOCK}]",)


@pytest.mark.parametrize("heads, tq", [(IDX_HEADS, 16), (4 * IDX_HEADS, 8)])
def test_a_chunks_scores_leave_the_kernel_as_their_readers_take_them(
        v5e, heads, tq):
    """``[2048, 32768]`` is a bitcast of what the kernel wrote wherever a
    grid row's queries are whole tiles of 8 rows: nothing of 268 MB is
    copied or transposed after the call (the form ``[grid rows, tiles, tq,
    1024]`` cost a transposition and a re-tiling at 16 queries a row)."""
    from tpudist.ops.flash_decode import paged_index_scores

    got, args = _chunk_scores(v5e, heads)
    assert got == tq
    hlo = _compile(paged_index_scores, *args)
    assert _kernel_calls(hlo) == 1
    assert f"= f32[{IDX_CHUNK // tq},{tq},{IDX_ENTRIES * BLOCK}]" in hlo
    assert not _big_f32_moves(hlo, IDX_CHUNK * IDX_ENTRIES * BLOCK)


def test_a_chunks_selection_reads_the_scores_where_the_kernel_wrote_them(
        v5e):
    """``paged_index_scores`` then ``index_select_mask`` at a chunk's
    shapes: the two kernels, and between and after them no copy and no
    transposition of the 2048 x 32768 scores (the threshold kernel's
    operand and the elementwise mask pass read the one array)."""
    from tpudist.ops.flash_decode import (index_select_mask,
                                          paged_index_scores)

    def chosen(q, w, pool, table, seen, rows):
        scores = paged_index_scores(q, w, pool, table, seen)
        return index_select_mask(scores, IDX_TOPK, rows=rows)

    _, args = _chunk_scores(v5e, IDX_HEADS)
    hlo = _compile(chosen, *args, _sds(v5e, (), jnp.int32))
    assert _kernel_calls(hlo) == 2
    assert re.search(r"%paged_index_scores[.\d]* = ", hlo)
    assert re.search(r"%index_select_threshold[.\d]* = ", hlo)
    assert not _big_f32_moves(hlo, IDX_CHUNK * IDX_ENTRIES * BLOCK)


def test_a_page_of_64_wide_index_keys_is_refused(v5e):
    """Why the cached index key is 128 wide (``index_cache_width``): the
    chip's compiler refuses the copy of a page narrower than a tile."""
    from tpudist.ops.flash_decode import paged_index_scores

    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(
            paged_index_scores, _sds(v5e, (IDX_LANES, IDX_HEADS, 64)),
            _sds(v5e, (IDX_LANES, IDX_HEADS), jnp.float32),
            _sds(v5e, (IDX_BLOCKS, BLOCK, 64)),
            _sds(v5e, (IDX_LANES, IDX_ENTRIES), jnp.int32),
            _sds(v5e, (IDX_LANES,), jnp.int32))


def test_sparse_gqa_attend_at_the_cells_shapes(v5e):
    """The chosen rows of the ONE pool of K and V gathered by flat row
    id, once, as rows of 32-bit words (the staged rows out of the one side
    buffer), and attended as pages of the gathered buffer, a tile's keys
    and values the two halves of its words, on the shared walk under its
    own name."""
    from benchmarks.layer_metrics import _index_spans
    from benchmarks.layer_metrics.paged_decode_us_per_call import KERNEL
    from tpudist.ops.flash_decode import kv_row, sparse_gqa_attend

    width, words = kv_row(IDX_KV * IDX_D, jnp.bfloat16)
    assert (width, words) == (IDX_KV * IDX_D, jnp.uint32)
    hlo = _compile(
        lambda q, kv, i, c, side: sparse_gqa_attend(
            q, kv, i, c, packed_kv_heads=IDX_KV, side_kv=side),
        _sds(v5e, (IDX_LANES, IDX_Q, IDX_D)),
        _sds(v5e, (IDX_BLOCKS * BLOCK, width), words),
        _sds(v5e, (IDX_LANES, IDX_TOPK), jnp.int32),
        _sds(v5e, (IDX_LANES,), jnp.int32),
        _sds(v5e, (IDX_LANES, IDX_SIDE, width), words))
    assert _kernel_calls(hlo) == 1
    op = _named_call(hlo, "sparse_gqa_attend", _index_spans.ATTEND)
    assert op["pallas"] and op["operands"] == 3   # meta, q, the rows
    assert op["outputs"] == (
        f"bf16[{IDX_LANES * IDX_KV},{IDX_Q // IDX_KV},{IDX_D}]",)
    assert not any(KERNEL.match(l.strip()) for l in hlo.splitlines())
    # ONE gather of the chosen rows, K and V of a token in one row of words
    assert len(re.findall(
        rf"= u32\[{IDX_LANES},{IDX_TOPK},{width}\]\S* gather\(", hlo)) == 1


@pytest.mark.parametrize("kv_heads", [IDX_KV, 2 * IDX_KV])
def test_every_row_walk_of_the_one_pool_at_the_cells_shapes(v5e, kv_heads):
    """The every-row branch of an indexer's layer: ``paged_flash_decode``
    over the one pool of K and V, rows of 32-bit words, and its one side
    buffer.  Four K/V heads of 128: a lane a grid row, a page one copy of
    2 KB rows, the tile slots the 4 MiB the two pools' were.  Eight: two
    rows a lane, each ONE copy of its heads' words."""
    from benchmarks.layer_metrics.paged_decode_us_per_call import KERNEL
    from tpudist.ops.flash_decode import (kv_row, paged_flash_decode,
                                          paged_grid_rows)

    width, words = kv_row(kv_heads * IDX_D, jnp.bfloat16)
    heads = IDX_Q * kv_heads // IDX_KV
    hlo = _compile(
        lambda q, kv, t, n, side, sl: paged_flash_decode(
            q, kv, None, t, n, packed_kv_heads=kv_heads, side_k=side,
            side_len=sl),
        _sds(v5e, (IDX_LANES, 1, heads, IDX_D)),
        _sds(v5e, (IDX_BLOCKS, BLOCK, width), words),
        _sds(v5e, (IDX_LANES, IDX_ENTRIES), jnp.int32),
        _sds(v5e, (IDX_LANES,), jnp.int32),
        _sds(v5e, (IDX_LANES, IDX_SIDE, width), words),
        _sds(v5e, (), jnp.int32))
    assert _kernel_calls(hlo) == 1
    op = _named_call(hlo, "paged_flash_decode", KERNEL)
    # meta, q, the pool, the side buffer (one block of words)
    assert op["pallas"] and op["operands"] == 4
    assert paged_grid_rows(IDX_LANES, kv_heads, IDX_D, BLOCK,
                           IDX_ENTRIES) == IDX_LANES * kv_heads // IDX_KV


def test_flash_chosen_rows_at_the_cells_shapes(v5e):
    """A sparse chunk's attention: 2048 queries at a dynamic offset over
    the batch-1 cache's 32768 rows under an int8 mask a (query, row)."""
    from benchmarks.layer_metrics import _index_spans
    from tpudist.ops.flash_attention import flash_chosen_rows

    kv = _sds(v5e, (1, IDX_ENTRIES * BLOCK, IDX_KV, IDX_D))
    hlo = _compile(
        lambda q, k, v, m, o: flash_chosen_rows(q, k, v, m, o),
        _sds(v5e, (1, IDX_CHUNK, IDX_Q, IDX_D)), kv, kv,
        _sds(v5e, (IDX_CHUNK, IDX_ENTRIES * BLOCK), jnp.int8),
        _sds(v5e, (), jnp.int32))
    assert _kernel_calls(hlo) == 1
    op = _named_call(hlo, "sparse_gqa_prefill", _index_spans.CHUNK_ATTEND)
    assert op["pallas"] and op["operands"] == 5   # offset, q, k, v, mask


@pytest.mark.parametrize("path", ["decode_step", "prefill_chunk"])
def test_index_select_at_the_cells_shapes(v5e, path):
    """The decode step's selection over the table's reach and the staged
    rows (positions), a chunk's over the cache's rows (a mask, with passes
    that follow the rows): ONE kernel holds every counting pass, so no
    sort of the row and no loop of XLA's is left around it."""
    from benchmarks.layer_metrics.index_select_chunk_ms_per_call import (
        KERNEL)
    from tpudist.ops.flash_decode import index_select, index_select_mask

    if path == "decode_step":
        t = IDX_LANES
        scores = _sds(v5e, (t, IDX_ENTRIES * BLOCK + IDX_SIDE), jnp.float32)
        hlo = _compile(lambda s: index_select(s, IDX_TOPK), scores)
        # what it replaces is one
        assert re.search(r"\bsort\(", _compile(
            lambda s: jax.lax.top_k(s, IDX_TOPK)[1], scores))
    else:
        t = IDX_CHUNK
        hlo = _compile(
            lambda s, n: index_select_mask(s, IDX_TOPK, rows=n),
            _sds(v5e, (t, IDX_ENTRIES * BLOCK), jnp.float32),
            _sds(v5e, (), jnp.int32))
    assert not re.search(r"\bsort\(", hlo) and _kernel_calls(hlo) == 1
    op = _named_call(hlo, "index_select_threshold", KERNEL)
    assert op["pallas"] and op["operands"] == 2          # rows, scores
    assert op["outputs"] == (f"s32[{t},1]",) * 2         # tau, last
    # the 32 value passes and the column passes were `while`s whose bodies
    # reduced a [T, W] or a [T, 2048] operand to a count a row: no loop at
    # all is left (what places a step's columns is products and sums)
    assert not re.search(r"\bwhile\(", hlo)


@pytest.mark.parametrize("shape, block", [
    ((IDX_CHUNK, IDX_ENTRIES * BLOCK), 32),
    ((IDX_LANES, IDX_ENTRIES * BLOCK + 128), 16)],
    ids=["prefill_chunk", "decode_step"])
def test_index_select_threshold_fits_vmem_at_the_widest_shapes(
        v5e, shape, block):
    """The cell's widest calls of the kernel alone: a block of queries'
    scores twice (the pipeline's two buffers) and their keys in VMEM,
    12 MiB for a chunk's block of 32 rows."""
    from tpudist.ops.flash_decode import _select_queries, _select_threshold

    assert _select_queries(*shape) == block
    hlo = _compile(
        lambda s, n: _select_threshold(s, n, k=IDX_TOPK, interpret=False),
        _sds(v5e, shape, jnp.float32), _sds(v5e, (), jnp.int32))
    assert _kernel_calls(hlo) == 1


def _indexer_loop(**over):
    from tpudist.models import MoEConfig

    moe = MoEConfig(num_experts=16, top_k=4, experts="gated_silu", d_ff=128,
                    scoring="softmax", held=(0, 4))
    sizes = dict(index_heads=16, index_head_dim=64, index_topk=1024)
    sizes.update(over)
    cfg = TransformerConfig(
        vocab_size=1024, num_layers=2, num_heads=8, num_kv_heads=2,
        head_size=128, embed_dim=512, max_seq_len=4096,
        compute_dtype=jnp.bfloat16, norm="rmsnorm", positions="rotary",
        rope_theta=1e7, mlp="gated_silu", mlp_dim=128, moe=moe,
        qk_norm=True, **sizes)
    return ServeLoop(cfg, _abstract_params(TransformerLM(cfg)),
                     num_slots=SLOTS, steps_per_sync=WIN_STEPS,
                     decode_attention="flash", prefill_chunk=1024,
                     cache_layout="paged", kv_block_size=BLOCK)


def _indexer_programs(v5e, loop):
    seg = _on(v5e, (loop.params, loop.cache, loop._tok, loop._active,
                    loop._remaining, loop._first, loop._key,
                    jnp.int32(WIN_STEPS), jnp.bool_(False)))
    chunk = _on(v5e, (loop.params, loop._blank1,
                      jnp.zeros((1, 1024), jnp.int32), jnp.int32(0),
                      jnp.int32(0)))
    return seg, chunk


def test_indexer_model_names_its_kernels(v5e):
    """Grouped queries with a q/k norm and an indexer that keeps 1024 of up
    to 4096 rows (Keye-VL 2.0's block at a small size): the segment holds
    the dense kernel (no lane past 1024 rows) and the routines of the
    chosen rows; the prefill chunk the dense flash pass (a prompt's first
    chunk) and the sparse one; the finish moves three leaves a layer."""
    loop = _indexer_loop()
    seg, chunk = _indexer_programs(v5e, loop)
    experts = {"moe_experts_gate_up", "moe_experts_down"}
    assert _kernel_scopes(loop._segment, *seg) == (
        {"paged_flash_decode", "paged_index_scores", "index_select_threshold",
         "sparse_gqa_attend"} | experts)
    assert _kernel_scopes(loop._prefill_chunk, *chunk, chunk=1024) == (
        {"flash_fwd", "paged_index_scores", "index_select_threshold",
         "sparse_gqa_prefill"} | experts)
    # two layers of four attention kernels and two expert kernels
    assert _kernel_calls(_compile(loop._segment, *seg)) == 12
    node = loop.cache["block0"]["attn"]
    assert node["paged_ikey"].shape[1:] == (BLOCK, 128)
    assert loop._blank1["block0"]["attn"]["cached_ikey"].shape == (
        1, 4096, 128)
    pages = jnp.zeros((32,), jnp.int32)
    finish = _on(v5e, (loop.cache, loop._tok, loop._active, loop._remaining,
                       loop._first, loop._blank1,
                       jnp.zeros((1, 1, 1024), jnp.float32), jnp.int32(0),
                       jnp.int32(5), jnp.int32(0), jnp.int32(5), pages,
                       jnp.int32(0), loop._key))
    _compile(loop._admit_finish, *finish)


def test_a_model_without_an_indexer_lowers_as_before(v5e):
    """``index_topk=None`` is the program of PR 32: stating the three
    indexer sizes as None changes no line of the lowered segment or chunk,
    neither holds the selection's scope or a conditional on the lanes'
    lengths, and an indexer changes both."""
    def texts(**kw_sizes):
        debug = kw_sizes.pop("debug_info", False)
        loop = _indexer_loop(**kw_sizes)
        seg, chunk = _indexer_programs(v5e, loop)
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            both = (loop._segment.lower(*seg).as_text(debug_info=debug),
                    loop._prefill_chunk.lower(*chunk, chunk=1024).as_text(
                        debug_info=debug))
        # a kernel's serialized body carries source lines and a counter
        return tuple(re.sub(r'backend_config = "[^"]*"', "", t)
                     for t in both)

    none = dict(index_heads=None, index_head_dim=None, index_topk=None)
    plain = texts(**none)
    for text in plain:
        assert "stablehlo.case" not in text
        assert "paged_index_scores" not in text
    with_indexer = texts()
    assert all("stablehlo.case" in t for t in with_indexer)
    # the scope as a name stack has it (``.../index_select/...``): the bare
    # word also names a frame of the tracebacks that jaxprs cached by an
    # indexer traced earlier in the process carry into later programs
    for mine, theirs in zip(texts(debug_info=True, **none),
                            texts(debug_info=True)):
        assert "/index_select/" not in mine and "/index_select/" in theirs


def test_dense_layout_segment_names_its_kernel(v5e):
    cfg = _cfg(4, 1)
    loop = ServeLoop(cfg, _abstract_params(TransformerLM(cfg)),
                     num_slots=SLOTS, steps_per_sync=STEPS,
                     decode_attention="flash", prefill_chunk=CHUNK)
    args = _segment_args(v5e, loop)
    assert _kernel_scopes(loop._segment, *args) == {"flash_decode"}


def test_composed_train_step_names_its_kernels(v5e):
    """The composed ``dp=1`` step with the flash kernels and ``remat``, as
    the training cell builds it, two layers deep."""
    import optax

    from tpudist.ops.flash_attention import flash_attention_fn
    from tpudist.ops.losses import cross_entropy
    from tpudist.parallel import MeshSpec, make_composed_train_step
    from tpudist.train.state import TrainState

    model = TransformerLM(dataclasses.replace(_cfg(4, 1), num_layers=2),
                          attention_fn=flash_attention_fn(), remat=True)

    def loss_fn(params, batch, rng):
        x, y = batch
        return cross_entropy(model.apply({"params": params}, x), y), {}

    spec = MeshSpec.parse("dp=1")
    with mock.patch.object(jax, "devices",
                           lambda *a: list(v5e.device_set)):
        mesh = spec.build()
    step = make_composed_train_step(spec, mesh, loss_fn)
    params = _abstract_params(model)
    state = jax.eval_shape(
        lambda p: TrainState.create(model.apply, p, optax.adamw(1e-4),
                                    rng=0), params)
    batch = jax.ShapeDtypeStruct((1, SEQ), jnp.int32)
    args = (_on(NamedSharding(mesh, P()), state),
            *_on(NamedSharding(mesh, spec.batch_spec()), (batch, batch)))
    scopes = _kernel_scopes(step, *args)
    assert scopes == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    # remat keeps the forward kernel's output and log-sum-exp, so the
    # COMPILED step runs each kernel once a layer: the backward pass's
    # recomputation holds no second flash_fwd
    hlo = _compile(step, *args)
    calls = {name: len(re.findall(rf"^\s*%{name}[.\d]* = .*custom-call\(",
                                  hlo, re.M))
             for name in scopes}
    assert calls == {"flash_fwd": 2, "flash_bwd_dq": 2, "flash_bwd_dkv": 2}
    # and the MLP's pre-activation, so a layer holds two products as wide as
    # ``ffn_dim`` (``up``, and the gradient of ``down``'s input) and no
    # second ``up``: 4 in two layers where a recomputed ``up`` made 6
    # (the compiler folds the one row away)
    assert _entry_products(hlo, f"bf16[{SEQ},{4 * EMBED}]") == 4


# Off this PR's path (ROADMAP R1 and D7): compiled and REPORTED, not gated —
# a refusal is a skip that carries the compiler's message.

def _compile_or_report(fn, *args) -> str:
    try:
        return _compile(fn, *args)
    except Exception as e:  # noqa: BLE001 - whatever the compiler raises
        pytest.skip(f"the v5e compiler refused it: {e}"[:600])


def test_report_fused_moe_mlp(v5e):
    from tpudist.ops.moe_dispatch import fused_moe_mlp

    tokens, d, f, experts, top_k = 4096, 512, 2048, 8, 2
    hlo = _compile_or_report(
        lambda x, wu, wd, i, g: fused_moe_mlp(x, wu, wd, i, g),
        _sds(v5e, (tokens, d)), _sds(v5e, (experts, d, f)),
        _sds(v5e, (experts, f, d)), _sds(v5e, (tokens, top_k), jnp.int32),
        _sds(v5e, (tokens, top_k), jnp.float32))
    assert _kernel_calls(hlo) >= 1


def test_report_fused_group_norm(v5e):
    from tpudist.ops.group_norm import group_norm_add_relu

    # a ResNet50 stage-1 Bottleneck tail at batch 128 @ 128 px
    x = _sds(v5e, (128, 32, 32, 256))
    c = _sds(v5e, (256,), jnp.float32)

    def loss(x, scale, bias, res):
        return jnp.sum(
            group_norm_add_relu(x, scale, bias, res).astype(jnp.float32))

    hlo = _compile_or_report(jax.grad(loss, argnums=(0, 1, 2, 3)), x, c, c, x)
    assert _kernel_calls(hlo) >= 2  # forward and backward kernels


# -- the routine scopes on the compiled serve programs (PR 37) --------------
# ``obs.ROUTINE_SCOPES`` on the three programs of the four families the
# benchmark serves (GPTBigCode; latent attention + experts; window +
# grouped queries + experts; an indexer).  Scopes are metadata: the
# programs with every piece of metadata taken out are the parent's, hash
# for hash.

SCOPE_STEPS = 16     # the recorded hashes are of 16-step segments
_MOE = dict(num_experts=16, top_k=4, experts="gated_silu", d_ff=128,
            held=(0, 4))
_BASE = dict(vocab_size=1024, embed_dim=512, compute_dtype=jnp.bfloat16)
_ROTARY = dict(norm="rmsnorm", positions="rotary", mlp="gated_silu")

# family -> (config, prefill chunk, the kernels of segment / chunk by the
# scope each must carry, the scopes its three programs must show between
# them, recorded hashes of segment / chunk / finish with metadata out)
FAMILIES = {
    "gptbigcode": (
        lambda: TransformerConfig(num_layers=2, num_heads=4, num_kv_heads=1,
                                  max_seq_len=2048, **_BASE),
        512, {"paged_flash_decode": "attn/core", "flash_fwd": "attn/core"},
        {"attn/proj", "attn/cache", "attn/core", "mlp/dense", "head"},
        ("54a131884cd7e181", "61089b8ea4eb6231", "d5b3b7579d73be0d")),
    "latent_experts": (
        lambda: TransformerConfig(
            num_layers=2, num_heads=8, max_seq_len=2048,
            rope_scaling=YarnScaling(mscale_all_dim=1.0), mlp_dim=1024,
            mla=MLAConfig(384, 512, 128, 64, 128),
            moe=MoEConfig(scoring="sigmoid", n_group=4, topk_group=2,
                          routed_scale=2.5, correction_bias=True, n_shared=1,
                          **_MOE),
            first_k_dense=1, **_BASE, **_ROTARY),
        512, {"paged_mla_decode": "attn/core", "flash_fwd": "attn/core",
              "moe_experts_gate_up": "mlp/experts",
              "moe_experts_down": "mlp/experts"},
        {"attn/proj", "attn/cache", "attn/core", "mlp/dense", "mlp/route",
         "mlp/experts", "mlp/shared", "head"},
        ("7c7c358efe200325", "abca54c6f1eedc19", "b5f9ede904d8226c")),
    "window_experts": (
        lambda: TransformerConfig(
            num_layers=4, num_heads=8, num_kv_heads=2, head_size=128,
            max_seq_len=4096, rope_theta=500000.0,
            rope_scaling=YarnScaling(16.0, 8192, 32.0, 1.0, 1.0, 0.0),
            window_rope_scaling=None,
            layer_windows=(1024, 1024, 1024, None), mlp_dim=128,
            moe=MoEConfig(scoring="softmax", **_MOE), **_BASE, **_ROTARY),
        512, {"paged_window_decode": "attn/core",
              "paged_flash_decode": "attn/core", "flash_fwd": "attn/core",
              "moe_experts_gate_up": "mlp/experts",
              "moe_experts_down": "mlp/experts"},
        {"attn/proj", "attn/cache", "attn/core", "mlp/route", "mlp/experts",
         "head"},
        ("307dda5a23aec04d", "9310ac3f7a357b0f", "c099691945c5e635")),
    "indexer": (
        lambda: TransformerConfig(
            num_layers=2, num_heads=8, num_kv_heads=2, head_size=128,
            max_seq_len=4096, rope_theta=1e7, mlp_dim=128,
            moe=MoEConfig(scoring="softmax", **_MOE), qk_norm=True,
            index_heads=16, index_head_dim=64, index_topk=1024, **_BASE,
            **_ROTARY),
        1024, {"paged_flash_decode": "attn/core",
               "paged_index_scores": "attn/index",
               "index_select_threshold": "attn/index",
               "sparse_gqa_attend": "attn/core", "flash_fwd": "attn/core",
               "sparse_gqa_prefill": "attn/core",
               "moe_experts_gate_up": "mlp/experts",
               "moe_experts_down": "mlp/experts"},
        {"attn/proj", "attn/cache", "attn/index", "attn/rows", "attn/core",
         "mlp/route", "mlp/experts", "head"},
        ("0c4825c511bff81e", "9fbef28d9e9d9c60", "21b9b593b1054222")),
}
PROGRAMS = ("_segment_impl", "_prefill_chunk_impl", "_admit_finish_impl")
# instructions that carry no routine, of those that are not parameters,
# constants, tuples or bitcasts: the compiler's own whose consumers do not
# agree on one (broadcasts, converts inside fused computations) and the
# unscoped remainder (embedding, block norms, residual adds, the loop's
# bookkeeping, the page-table arithmetic of the insert).  By COUNT, at toy
# widths; what they cost on the chip is step_other_ms
UNSCOPED_SHARE = {"_segment_impl": 0.4, "_prefill_chunk_impl": 0.6,
                  "_admit_finish_impl": 0.4}

_METADATA = re.compile(r", metadata=\{[^}]*\}")
_TABLES = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*\n?",
    re.M)
_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
_OPCODE = re.compile(
    r"^\s*(?:ROOT )?%([^\s=]+) = (?:\([^=]*?\)|\S+) ([a-z][a-z\-]*)\(")
_NO_WORK = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}


def _mosaic_without_locations(b64: str) -> str:
    from jax._src.lib.mlir import ir

    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(b64))
        asm = module.operation.get_asm(enable_debug_info=False)
    return hashlib.sha256(asm.encode()).hexdigest()


def strip_metadata(hlo: str) -> str:
    """HLO text with all that is only metadata taken out: instruction
    metadata, the stack-frame tables, and the locations inside each Mosaic
    module (a Pallas call's serialized body is replaced by the hash of its
    assembly printed without debug info).  Names, shapes, layouts,
    operands and backend configs stay."""
    hlo = _METADATA.sub("", _TABLES.sub("", hlo))
    return _BODY.sub(
        lambda m: f'"body":"{_mosaic_without_locations(m.group(1))}"', hlo)


@pytest.fixture(scope="module")
def compiled(v5e):
    """``{family: {program: HLO text}}`` for the described device, each
    program at the shapes ``ServeLoop.serve_programs`` gives (a module's
    fixture is set up before a test's own ``_quiet_cache``)."""
    out = {}
    with _cache_off():
        for family, (make, chunk, *_) in FAMILIES.items():
            cfg = make()
            loop = ServeLoop(cfg, _abstract_params(TransformerLM(cfg)),
                             num_slots=SLOTS, steps_per_sync=SCOPE_STEPS,
                             decode_attention="flash", prefill_chunk=chunk,
                             cache_layout="paged", kv_block_size=BLOCK)
            out[family] = {
                name: _compile(jitted, *_on(v5e, args), **static)
                for name, (jitted, args, static)
                in loop.serve_programs().items()}
    return out


@pytest.mark.parametrize("family", FAMILIES)
def test_named_kernels_lie_under_their_routine(compiled, family):
    """Every Pallas call of segment and chunk keeps the instruction name a
    trace reader matches (``%<kernel>.<n> =``) and carries the scope the
    table gives its routine."""
    _, _, kernels, _, _ = FAMILIES[family]
    seen = set()
    for program in PROGRAMS[:2]:
        text = compiled[family][program]
        scopes = hlo_scopes(text)
        for line in text.splitlines():
            m = _OPCODE.match(line)
            if m and "tpu_custom_call" in line:
                name = re.sub(r"[.\d]+$", "", m.group(1))
                assert scopes.get(m.group(1)) == kernels[name], (
                    program, m.group(1))
                seen.add(name)
    assert seen == set(kernels)


@pytest.mark.parametrize("family", FAMILIES)
def test_every_routine_the_family_has_is_scoped(compiled, family):
    _, _, _, routines, _ = FAMILIES[family]
    found = set()
    for program in PROGRAMS:
        found |= set(hlo_scopes(compiled[family][program]).values())
    assert found == routines
    assert found <= set(obs.ROUTINE_SCOPES)
    # the finish moves the prefilled rows into the pool and picks the token
    assert set(hlo_scopes(compiled[family][PROGRAMS[2]]).values()) == {
        "attn/cache", "head"}


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("family", FAMILIES)
def test_unscoped_instructions_stay_under_the_stated_share(
        compiled, family, program):
    text = compiled[family][program]
    scopes = hlo_scopes(text)
    work = [m.group(1) for m in map(_OPCODE.match, text.splitlines())
            if m and m.group(2) not in _NO_WORK]
    bare = sum(1 for name in work if name not in scopes)
    assert work and bare / len(work) < UNSCOPED_SHARE[program], (
        bare, len(work))


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("family", FAMILIES)
def test_programs_without_metadata_are_the_parents(compiled, family,
                                                   program):
    """Operation for operation, name for name: the hash of the compiled
    text with its metadata stripped is the one recorded from the commit
    before the scopes (293f19f), on the same toy program.  The indexer's
    finish is recorded from the commit that made the ONE pool row of K and
    V a row of 32-bit words, its segment and chunk from the one that had
    ``paged_index_scores`` write a grid row's queries by all its columns
    (every other family's three stayed the text they were, both times)."""
    want = FAMILIES[family][4][PROGRAMS.index(program)]
    text = strip_metadata(compiled[family][program])
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want
