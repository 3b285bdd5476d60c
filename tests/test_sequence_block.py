"""The described block (ops/transformer.py ``BlockSpec``): a tiny instance
of the published pattern — three sliding-window layers and one full,
grouped-query heads, two rotary kinds, routed experts — held to the plain
float32 reference at logit level, and the sequence engine's resident
serving path around it."""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_predictionio_tpu.data.bimap import BiMap
from incubator_predictionio_tpu.models.sequence import reference
from incubator_predictionio_tpu.models.sequence.engine import (
    Query,
    SeqRecAlgorithm,
    SeqRecAlgorithmParams,
    SeqRecModel,
)
from incubator_predictionio_tpu.ops import attention, moe, topk
from incubator_predictionio_tpu.ops import transformer as T
from incubator_predictionio_tpu.utils import json_codec

VOCAB, LENGTH = 50, 24
PLAIN = T.Rotary(theta=10000.0)
YARN = T.Rotary(theta=10000.0, factor=4.0, original_max_position=16,
                beta_fast=32.0, beta_slow=1.0,
                attention_factor=0.1 * np.log(4.0) + 1.0)
TINY = T.BlockSpec(
    d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
    period=(T.LayerSpec(window=8, rotary=PLAIN),) * 3
    + (T.LayerSpec(rotary=YARN),),
    n_periods=1, ffn="routed-swiglu", ffn_width=32, n_experts=8,
    experts_per_token=2, tied_head=False, max_len=LENGTH)
#: the published numbers of the full layers' YaRN
PUBLISHED = T.Rotary(theta=500000.0, factor=16.0,
                     original_max_position=8192, beta_fast=32.0,
                     beta_slow=1.0, attention_factor=1.2772588722239782)


@pytest.fixture(scope="module")
def weights():
    return T.block_init(jax.random.key(11), TINY, VOCAB, LENGTH)


def windows_of(n: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    out = rng.integers(1, VOCAB, (n, LENGTH)).astype(np.int32)
    out[0, :5] = T.PAD          # one history shorter than its window
    return out


def reference_logits(spec, weights, window):
    sz = reference.sizes_of(spec, VOCAB)
    hidden = reference.forward_hidden(
        sz, weights.item_emb, weights.lnf_scale,
        reference.layers_of(spec, weights), window)
    return np.asarray(reference.head_logits(hidden, weights.head))


def test_logits_agree_with_the_plain_reference_at_every_position(weights):
    tokens = windows_of(3)
    hidden, routed = T.block_apply(TINY, weights, jnp.asarray(tokens))
    got = np.asarray(T.head_logits(TINY, weights, hidden))
    assert got.shape == (3, LENGTH, VOCAB)
    for b in range(3):
        want = reference_logits(TINY, weights, tokens[b])
        keep = tokens[b] != T.PAD     # a PAD position's output is unused
        assert np.abs(got[b][keep] - want[keep]).max() < 2e-4
    # every token of the dispatch went to two experts in every layer
    assert routed.shape == (4, 8)
    assert np.all(np.asarray(routed).sum(axis=1) == 3 * LENGTH * 2)


def test_two_periods_scan_as_eight_layers():
    spec = T.BlockSpec(**{**TINY.__dict__, "n_periods": 2})
    w = T.block_init(jax.random.key(5), spec, VOCAB, LENGTH)
    tokens = windows_of(2, seed=3)
    hidden, routed = T.block_apply(spec, w, jnp.asarray(tokens))
    assert routed.shape == (8, 8)
    got = np.asarray(T.head_logits(spec, w, hidden))[1]
    want = reference_logits(spec, w, tokens[1])
    assert np.abs(got - want).max() < 5e-4


def dense_attention(q, k, v, window):
    """softmax(q·k/√d) under a causal window mask, by hand."""
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    t, u = np.arange(q.shape[1])[:, None], np.arange(k.shape[1])[None, :]
    mask = (u <= t) & (t - u < window)
    s = np.where(mask, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("window", [1, 8, 24, 100])
def test_window_mask_against_a_dense_mask(window):
    rng = np.random.default_rng(window)
    q, k, v = (rng.normal(size=(2, 24, 2, 8)).astype(np.float32)
               for _ in range(3))
    got = attention.dot_product_attention(q, k, v, causal=True,
                                          window=window)
    assert np.abs(np.asarray(got)
                  - dense_attention(q, k, v, window)).max() < 1e-5


@pytest.mark.parametrize("window,block", [(300, 512), (1024, 512),
                                          (513, 256), (None, 512)])
def test_blockwise_skips_blocks_and_agrees_with_dense(window, block):
    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 1100, 2, 8)), jnp.float32)
               for _ in range(3))
    valid = jnp.asarray(rng.uniform(size=(1, 1100)) > 0.05)
    dense = attention.dot_product_attention(q, k, v, causal=True,
                                            kv_valid=valid, window=window)
    blocked = attention.blockwise_attention(
        q, k, v, causal=True, block_size=block, kv_valid=valid,
        window=window)
    assert np.abs(np.asarray(blocked) - np.asarray(dense)).max() < 1e-5
    # the default attention takes the scan over 1,024 positions
    routed = T._default_attn(q, k, v, causal=True, kv_valid=valid,
                             window=window)
    assert np.abs(np.asarray(routed) - np.asarray(dense)).max() < 1e-5


@pytest.mark.parametrize("window", [None, 300])
def test_the_tpu_attention_route_agrees_with_dense(window):
    """What a TPU runs for bfloat16 heads of 128 over more than 1,024
    positions, interpreted here: the rotary pass into the kernel's layout
    against ``_apply_rotary``, and the kernel that ships with jax —
    grouped heads in place, a window, padding keys — against the dense
    path on repeated heads."""
    b, s, h, h_kv, d = 2, 1024, 4, 2, 128
    rng = np.random.default_rng(3)
    rot = T.Rotary(theta=500000.0, factor=16.0, original_max_position=8192,
                   attention_factor=1.2772588722239782)
    cos, sin = T._rotary_tables(rot, d, s)
    x = jnp.asarray(rng.normal(size=(b, s, h * d)), jnp.bfloat16)
    turned = attention.rotate_heads_first(x, cos, sin, h, 0.5,
                                          interpret=True)
    want = T._apply_rotary(x.reshape(b, s, h, d), cos, sin)
    assert turned.shape == (b, h, s, d)
    assert np.abs(np.asarray(turned, np.float32)
                  - 0.5 * np.asarray(want, np.float32).transpose(0, 2, 1, 3)
                  ).max() < 0.02
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, n, d)), jnp.bfloat16)
               for n in (h, h_kv, h_kv))
    valid = jnp.arange(s)[None, :] >= jnp.asarray([[0], [37]])
    heads_first = lambda a: a.transpose(0, 2, 1, 3)
    got = heads_first(attention.kernel_attention(
        heads_first((q.astype(jnp.float32) * d ** -0.5).astype(q.dtype)),
        heads_first(k), heads_first(v), kv_valid=valid, window=window,
        interpret=True))
    f32 = lambda a: a.astype(jnp.float32)
    dense = attention.dot_product_attention(
        f32(q), f32(jnp.repeat(k, h // h_kv, 2)),
        f32(jnp.repeat(v, h // h_kv, 2)), causal=True, kv_valid=valid,
        window=window)
    gap = np.abs(np.asarray(got, np.float32) - np.asarray(dense))
    gap[1, :37] = 0     # what a padding query computes is unused
    assert gap.max() < 0.02
    assert not attention.kernel_attention_fits(jnp.bfloat16, 2048, 128)


#: block heights :func:`ops.moe.kernel_rows` can return, and the tile's own
KERNEL_HEIGHTS = (32, 64, 128, 256)


def expert_tables(e=8, d=256, f=128, rows=1024):
    ks = jax.random.split(jax.random.key(1), 4)
    w = moe.ExpertWeights(
        router=None,
        w_gate=(jax.random.normal(ks[0], (e, d, f)) * d ** -0.5
                ).astype(jnp.bfloat16),
        w_up=(jax.random.normal(ks[1], (e, d, f)) * d ** -0.5
              ).astype(jnp.bfloat16),
        w_down=(jax.random.normal(ks[2], (e, f, d)) * f ** -0.5
                ).astype(jnp.bfloat16))
    return w, jax.random.normal(ks[3], (rows, d), jnp.bfloat16)


@pytest.mark.parametrize("sizes", [[100, 0, 300, 28, 0, 340, 256, 0],
                                   [0, 0, 0, 0, 0, 0, 1, 1023],
                                   [128] * 8,
                                   # a group that ends inside the first
                                   # block, one that starts inside the last
                                   [3, 0, 500, 0, 0, 500, 0, 21],
                                   # one expert has every row
                                   [0, 0, 1024, 0, 0, 0, 0, 0]])
def test_the_tpu_expert_kernel_agrees_with_the_grouped_products(sizes):
    """One kernel over tiles of sorted rows, interpreted here, against
    three ``ragged_dot``, at every block height: tiles and blocks two
    experts share, blocks a visit skips, empty experts, one row."""
    w, xs = expert_tables()
    group_sizes = jnp.asarray(sizes, jnp.int32)
    want = moe.grouped_swiglu(xs, w, group_sizes)
    for rows in KERNEL_HEIGHTS:
        got = moe._experts_pallas(xs, w, group_sizes, rows, interpret=True)
        assert np.abs(np.asarray(got, np.float32)
                      - np.asarray(want, np.float32)).max() < 0.02


def test_a_rows_result_does_not_depend_on_the_block_it_rode_in():
    w, xs = expert_tables()
    group_sizes = jnp.asarray([100, 0, 300, 28, 0, 340, 255, 1], jnp.int32)
    first, *others = (
        np.asarray(moe._experts_pallas(xs, w, group_sizes, rows,
                                       interpret=True), np.float32)
        for rows in KERNEL_HEIGHTS)
    for other in others:
        assert np.array_equal(first, other)


@pytest.mark.parametrize("windows", range(1, 9))
def test_the_block_height_at_the_widths_the_cell_warms(windows):
    """2,048 tokens × 8 experts a token a window, 64 experts: whole
    tiles, whole blocks a tile, a height the kernel is tested at."""
    m = windows * 2048 * 8
    rows = moe.kernel_rows(m, 64)
    assert rows in KERNEL_HEIGHTS
    assert m % moe.KERNEL_TILE_ROWS == 0 and moe.KERNEL_TILE_ROWS % rows == 0
    assert rows == (32 if windows == 1 else 64)


@pytest.mark.parametrize("sizes", [
    [256, 128, 128, 512, 0, 0, 0, 0],           # on block boundaries
    [0, 0, 0, 1024, 0, 0, 0, 0],                # all in one expert
    [100, 0, 300, 28, 0, 340, 256, 0],          # empty experts between
    [127, 129, 128, 128, 131, 125, 128, 128],   # balanced
], ids=["aligned", "one-expert", "empty-experts", "balanced"])
def test_multiplied_rows_against_a_count_block_by_block(sizes):
    ends = np.cumsum(sizes)
    for rows in (16, 64, 128, 256):
        brute = sum(
            rows for at in range(0, int(ends[-1]), rows)
            for size, end in zip(sizes, ends)
            if size and end - size < at + rows and end > at)
        assert moe.rows_multiplied(sizes, rows) == brute
    assert moe.rows_multiplied(sizes, None) == 1024
    assert moe.rows_multiplied([256, 128, 128, 512, 0, 0, 0, 0], 128) == 1024


#: group-size patterns of 1,024 routed rows over eight experts: empty
#: experts, one expert with every row, groups ending inside a block
GROUP_PATTERNS = [[100, 0, 300, 28, 0, 340, 256, 0],
                  [0, 0, 0, 0, 0, 0, 1, 1023],
                  [3, 0, 500, 0, 0, 500, 0, 21],
                  [0, 0, 1024, 0, 0, 0, 0, 0]]


def sorted_assignments(sizes, k=8, seed=0):
    """(order, inverse, weights) of ``Σ sizes / k`` tokens, ``k``
    assignments each, whose experts come in the counts ``sizes``: what
    :func:`ops.moe.moe_apply` sorts and un-sorts."""
    rng = np.random.default_rng(seed)
    flat = rng.permutation(np.repeat(np.arange(len(sizes)), sizes))
    order = np.argsort(flat, kind="stable").astype(np.int32)
    logits = rng.normal(size=(len(flat) // k, k)).astype(np.float32)
    weights = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    return order, np.argsort(order).astype(np.int32), weights


@pytest.mark.parametrize("resident", [False, True],
                         ids=["streamed", "resident"])
@pytest.mark.parametrize("tokens", [256, 64, 32],
                         ids=["one-tile", "two-tiles", "four-tiles"])
@pytest.mark.parametrize("sizes", GROUP_PATTERNS,
                         ids=["empty-experts", "one-row", "inside-blocks",
                              "one-expert"])
def test_the_combine_kernel_agrees_with_the_gather_and_the_sum(
        sizes, tokens, resident, monkeypatch):
    """The experts' kernel's packed rows un-sorted and summed by the
    combine kernel (interpreted), k = 8, in one tile of tokens, two, or
    four — the rows streamed in a tile ahead, whose last tile must start
    no copies past it, or held in VMEM whole — against the same bfloat16
    rows gathered and summed in float32: in the order of j, and by the
    einsum — equal up to the float32 sum's order."""
    monkeypatch.setattr(moe, "COMBINE_TOKENS", tokens)
    monkeypatch.setattr(moe, "COMBINE_RESIDENT_BYTES",
                        (1 << 30) if resident else 0)
    w, xs = expert_tables()
    group_sizes = jnp.asarray(sizes, jnp.int32)
    _, inverse, weights = sorted_assignments(sizes)
    packed = moe._experts_pallas(xs, w, group_sizes, 32, packed=True,
                                 interpret=True)
    got = np.asarray(moe._combine_pallas(
        packed, jnp.asarray(inverse), jnp.asarray(weights), jnp.bfloat16,
        interpret=True), np.float32)
    rows = np.asarray(moe._experts_pallas(xs, w, group_sizes, 32,
                                          interpret=True), np.float32)
    per_choice = rows[inverse].reshape(len(weights), 8, -1)
    ordered = np.zeros(got.shape, np.float32)
    for j in range(8):
        ordered = ordered + weights[:, j:j + 1] * per_choice[:, j]
    gathered = jnp.einsum("tkd,tk->td", jnp.asarray(per_choice),
                          jnp.asarray(weights))
    # two float32 sums of the same eight products part in their last bit
    # at most (an FMA here, another order there), which moves the one
    # rounding to bfloat16 by a step, rarely
    for want in (ordered, gathered):
        want = np.asarray(jnp.asarray(want).astype(jnp.bfloat16), np.float32)
        step = np.abs(got - want)
        assert np.all(step <= 2.0 ** -7 * np.abs(want))
        assert (step > 0).mean() < 1e-3


@pytest.mark.parametrize("values", [
    "normal", "extremes", "signed-zeros-and-subnormals", "not-a-number",
])
def test_packed_rows_round_trip_bitwise(values):
    """``unpack_rows(pack_rows(ys))`` gives back every bfloat16 bit of
    ``ys``, its two halves side by side: what the combine kernel reads is
    what the experts' kernel wrote."""
    rng = np.random.default_rng(3)
    bits = {
        "normal": rng.normal(size=(16, 512)).astype(np.float32),
        "extremes": np.tile(np.float32([3.3895e38, -3.3895e38, np.inf,
                                        -np.inf, 1.0, -2.0, 0.5, 7.0]),
                            (16, 64)),
        "signed-zeros-and-subnormals": np.tile(np.float32(
            [0.0, -0.0, 1e-40, -1e-40, 9.2e-41, -5e-39, 1e-45, 1.2e-38]),
            (16, 64)),
        "not-a-number": np.where(rng.random((16, 512)) < 0.5, np.nan,
                                 rng.normal(size=(16, 512))
                                 ).astype(np.float32),
    }[values]
    ys = jnp.asarray(bits).astype(jnp.bfloat16)
    words = moe.pack_rows(ys)
    assert words.shape == (16, 256) and words.dtype == jnp.uint32
    low, high = moe.unpack_rows(words)
    back = jnp.concatenate([low, high], axis=1).astype(jnp.bfloat16)
    as_bits = lambda a: np.asarray(
        jax.lax.bitcast_convert_type(a, jnp.uint16))
    assert np.array_equal(as_bits(back), as_bits(ys))


@pytest.mark.parametrize("rows", KERNEL_HEIGHTS)
def test_packed_rows_unpack_to_the_kernels_bfloat16_rows(rows):
    w, xs = expert_tables()
    group_sizes = jnp.asarray(GROUP_PATTERNS[0], jnp.int32)
    plain = moe._experts_pallas(xs, w, group_sizes, rows, interpret=True)
    packed = moe._experts_pallas(xs, w, group_sizes, rows, packed=True,
                                 interpret=True)
    assert packed.shape == (1024, 1, 128) and packed.dtype == jnp.uint32
    assert np.array_equal(np.asarray(packed[:, 0]),
                          np.asarray(moe.pack_rows(plain)))
    low, high = moe.unpack_rows(packed[:, 0])
    assert np.array_equal(
        np.asarray(jnp.concatenate([low, high], axis=1)),
        np.asarray(plain, np.float32))


def test_the_expert_layer_through_both_kernels_gives_what_the_gather_gives(
        monkeypatch):
    """``moe_apply`` as a TPU runs it — the experts' kernel writing packed
    rows, the combine kernel un-sorting them, both interpreted here —
    against ``ragged_dot`` and the gather."""
    from incubator_predictionio_tpu.ops import pallas_kernels

    t, d, f, e, k = 128, 256, 128, 16, 8
    ks = jax.random.split(jax.random.key(5), 5)
    w = moe.ExpertWeights(
        router=jax.random.normal(ks[0], (d, e)) * d ** -0.5,
        w_gate=(jax.random.normal(ks[1], (e, d, f)) * d ** -0.5
                ).astype(jnp.bfloat16),
        w_up=(jax.random.normal(ks[2], (e, d, f)) * d ** -0.5
              ).astype(jnp.bfloat16),
        w_down=(jax.random.normal(ks[3], (e, f, d)) * f ** -0.5
                ).astype(jnp.bfloat16))
    h = jax.random.normal(ks[4], (t, d), jnp.bfloat16)
    want, want_counts = moe.moe_apply(h, w, k)
    monkeypatch.setattr(pallas_kernels, "pallas_available", lambda: True)
    for name in ("_experts_pallas", "_combine_pallas"):
        monkeypatch.setattr(moe, name, functools.partial(
            getattr(moe, name), interpret=True))
    assert moe.combine_serves(t * k, d, h.dtype)
    got, counts = moe.moe_apply(h, w, k)
    assert got.dtype == jnp.bfloat16 and got.shape == (t, d)
    assert np.array_equal(np.asarray(counts), np.asarray(want_counts))
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32)).max() < 0.02


@pytest.mark.parametrize("windows", range(1, 9))
def test_the_combine_kernel_serves_every_width_the_cell_warms(
        windows, monkeypatch):
    from incubator_predictionio_tpu.ops import pallas_kernels

    monkeypatch.setattr(pallas_kernels, "pallas_available", lambda: True)
    assert moe.combine_serves(windows * 2048 * 8, 2304, jnp.bfloat16)


def test_the_combine_kernel_refuses_what_it_cannot_take(monkeypatch):
    from incubator_predictionio_tpu.ops import pallas_kernels

    assert not moe.combine_serves(16384, 2304, jnp.bfloat16)   # no TPU
    monkeypatch.setattr(pallas_kernels, "pallas_available", lambda: True)
    assert moe.combine_serves(16384, 2304, jnp.bfloat16)
    assert not moe.combine_serves(16384, 2176, jnp.bfloat16)   # half a lane
    assert not moe.combine_serves(16384, 2304, jnp.float32)
    assert not moe.combine_serves(16384 + 8, 2304, jnp.bfloat16)


def test_blockwise_with_a_window_differentiates():
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 40, 1, 4)), jnp.float32)
               for _ in range(3))

    def loss(fn, q):
        return (fn(q) ** 2).sum()

    dense = jax.grad(lambda q: loss(lambda x: attention.
                                    dot_product_attention(
                                        x, k, v, window=9), q))(q)
    blocked = jax.grad(lambda q: loss(lambda x: attention.
                                      blockwise_attention(
                                          x, k, v, block_size=16,
                                          window=9), q))(q)
    assert np.abs(np.asarray(dense) - np.asarray(blocked)).max() < 1e-4


def test_plain_rotary_is_the_formula():
    inv = T.rotary_inv_freq(T.Rotary(theta=500000.0), 128)
    want = 500000.0 ** (-2.0 * np.arange(64) / 128)
    assert np.allclose(inv, want, rtol=1e-12)
    # rotate-half by the angle pos·inv_freq: pairs (i, i + 64) turn as
    # complex numbers
    x = np.random.default_rng(0).normal(size=(1, 5, 1, 128)).astype(
        np.float32)
    cos, sin = T._rotary_tables(T.Rotary(theta=500000.0), 128, 5)
    got = np.asarray(T._apply_rotary(jnp.asarray(x), cos, sin))[0, :, 0]
    z = (x[0, :, 0, :64] + 1j * x[0, :, 0, 64:]) * np.exp(
        1j * np.arange(5)[:, None] * want[None, :])
    assert np.allclose(got[:, :64], z.real, atol=1e-5)
    assert np.allclose(got[:, 64:], z.imag, atol=1e-5)


def test_yarn_rotary_is_the_formula():
    inv = T.rotary_inv_freq(PUBLISHED, 128)
    i = np.arange(64)
    pos = 500000.0 ** (2.0 * i / 128)

    def d(n):
        return 128 * np.log(8192 / (2 * np.pi * n)) / (2 * np.log(500000.0))

    low, high = np.floor(d(32)), np.ceil(d(1))
    assert (low, high) == (18, 35)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    assert np.allclose(inv, ramp / (16 * pos) + (1 - ramp) / pos,
                       rtol=1e-12)
    # fast dimensions keep their frequency, slow ones are interpolated
    assert np.allclose(inv[:18], 1 / pos[:18])
    assert np.allclose(inv[35:], 1 / (16 * pos[35:]))
    cos, sin = T._rotary_tables(PUBLISHED, 128, 3)
    assert float(cos[0, 0]) == pytest.approx(1.2772588722239782)
    assert np.allclose(np.asarray(sin[2, :64]),
                       1.2772588722239782 * np.sin(2 * inv), atol=1e-6)


def test_expert_layer_against_a_loop_over_tokens_with_empty_experts():
    rng = np.random.default_rng(4)
    t, d, f, e, k = 37, 16, 8, 8, 2
    h = rng.normal(size=(t, d)).astype(np.float32)
    h[:, 0] = 1.0
    router = rng.normal(size=(d, e)).astype(np.float32)
    # planted skew: experts 5, 6 and 7 are never among a token's best two
    router[:, 5:] = 0.0
    router[0, 5:] = -50.0
    w = moe.ExpertWeights(
        router=jnp.asarray(router),
        w_gate=jnp.asarray(rng.normal(size=(e, d, f)), jnp.float32),
        w_up=jnp.asarray(rng.normal(size=(e, d, f)), jnp.float32),
        w_down=jnp.asarray(rng.normal(size=(e, f, d)), jnp.float32))
    got, counts = moe.moe_apply(jnp.asarray(h), w, k)
    want = np.zeros((t, d))
    seen = np.zeros(e, int)
    for n in range(t):
        logits = h[n].astype(np.float64) @ router
        p = np.exp(logits - logits.max())
        p /= p.sum()
        best = np.argsort(-p)[:k]
        for ex in best:
            x = h[n].astype(np.float64)
            g = x @ np.asarray(w.w_gate[ex], np.float64)
            act = g / (1 + np.exp(-g)) * (x @ np.asarray(w.w_up[ex],
                                                         np.float64))
            want[n] += p[ex] / p[best].sum() * (
                act @ np.asarray(w.w_down[ex], np.float64))
            seen[ex] += 1
    assert np.array_equal(np.asarray(counts), seen)
    assert seen[5:].sum() == 0 and seen.sum() == t * k
    assert np.abs(np.asarray(got) - want).max() < 1e-3 * np.abs(want).max()


def test_sasrec_is_an_instance_and_gives_what_it_gave():
    """The forward the SASRec block had before it became an instance of
    the description, written out, against ``transformer_apply``: same
    seed, same numbers."""
    w = T.transformer_init(jax.random.key(3), 50, 12, 32, 3)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 51, (4, 12)),
                         jnp.int32)
    n_heads, (b, l), d = 4, tokens.shape, 32

    def norm(x, scale):
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + 1e-6) * scale

    x = w.item_emb[tokens] + w.pos_emb[:l]
    valid = tokens != T.PAD
    for n in range(3):
        h = norm(x, w.ln1_scale[n])
        q, k, v = ((h @ m[n]).reshape(b, l, n_heads, d // n_heads)
                   for m in (w.wq, w.wk, w.wv))
        o = attention.dot_product_attention(q, k, v, causal=True,
                                            kv_valid=valid)
        x = x + o.reshape(b, l, d) @ w.wo[n]
        h = norm(x, w.ln2_scale[n])
        x = x + jax.nn.gelu(h @ w.w_up[n]) @ w.w_down[n]
    want = np.asarray(norm(x, w.lnf_scale))
    got = np.asarray(T.transformer_apply(w, tokens, n_heads))
    assert np.allclose(got, want, rtol=0, atol=1e-5)
    spec, block = T.sasrec_block(w, n_heads)
    assert (spec.n_layers, spec.ffn, spec.tied_head, spec.max_len) == (
        3, "dense-gelu", True, 12)
    hidden, routed = T.block_apply(spec, block, tokens)
    assert np.array_equal(np.asarray(hidden), got)
    assert routed.shape == (3, 0)


def test_the_description_travels_as_engine_parameters():
    doc = json_codec.to_jsonable(TINY)
    assert doc["period"][0] == {"window": 8, "rotary": json_codec.
                                to_jsonable(PLAIN)}
    assert T.block_spec_from_json(json.loads(json.dumps(doc))) == TINY
    with pytest.raises(ValueError):
        T.block_spec_from_json({**doc, "ffn": "dense-relu"})
    with pytest.raises(ValueError):
        T.block_spec_from_json({**doc, "nKvHeads": 3})


# -- the engine around it ---------------------------------------------------

N_USERS = 12


@pytest.fixture()
def served(weights):
    algo = SeqRecAlgorithm(SeqRecAlgorithmParams(
        app_name=None, block=json_codec.to_jsonable(TINY)))
    model = SeqRecModel(
        weights=weights,
        item_bimap=BiMap({f"i{t + 1}": t for t in range(VOCAB - 1)}),
        n_heads=TINY.n_heads, max_len=LENGTH, final_loss=0.0, spec=TINY,
        windows=windows_of(N_USERS, seed=9),
        user_bimap=BiMap({f"u{r}": r for r in range(N_USERS)}))
    return algo, algo.prepare_model(None, model)


def object_bytes(algo, model, docs) -> list:
    """What the server's object path renders for the same fused batch:
    ``batch_predict`` (one query: ``predict``), then the codec."""
    queries = [(i, json_codec.extract(Query, d))
               for i, d in enumerate(docs)]
    got = dict(algo.batch_predict(model, queries)) if len(docs) > 1 \
        else {0: algo.predict(model, queries[0][1])}
    return [json.dumps(json_codec.to_jsonable(got[i])).encode()
            for i in range(len(docs))]


def test_fused_batch_bytes_equal_the_object_path(served):
    algo, model = served
    docs = [{"user": "u3", "num": 10}, {"user": "u0", "num": 4},
            {"user": "u11", "num": 10}, {"user": "u3", "num": 1},
            {"user": "u7", "num": 16}]
    got = algo.batch_serve_json(model, docs)
    assert all(isinstance(b, bytes) for b in got)
    assert got == object_bytes(algo, model, docs)
    assert algo.batch_serve_json(model, docs[:1]) == object_bytes(
        algo, model, docs[:1])
    for doc, body in zip(docs, got):
        rows = json.loads(body)["itemScores"]
        assert len(rows) == doc["num"]
        window = model.windows[model.user_bimap[doc["user"]]]
        served_tokens = {int(r["item"][1:]) for r in rows}
        assert not served_tokens & set(np.asarray(window).tolist())
    # the answer is the reference's: the best logits after the exclusions
    window = np.asarray(model.windows[3])
    logits = reference_logits(TINY, model.weights, window)[-1].copy()
    logits[0] = -np.inf
    logits[window] = -np.inf
    rows = json.loads(got[0])["itemScores"]
    assert [int(r["item"][1:]) for r in rows] == \
        np.argsort(-logits)[:10].tolist()
    assert np.allclose([r["score"] for r in rows],
                       np.sort(logits)[::-1][:10], atol=2e-4)


def test_what_cannot_be_answered_from_residence_takes_the_object_path(
        served, monkeypatch):
    algo, model = served
    docs = [{"user": "u1", "num": 3},
            {"user": "nobody", "num": 3},
            {"user": "u1", "num": 3, "recentItems": ["i4", "i9"]},
            {"user": "u1", "num": 0}, {"user": 7, "num": 3}, "junk"]
    got = algo.batch_serve_json(model, docs)
    assert isinstance(got[0], bytes) and got[1:] == [None] * 5
    # an explicit history is read, not the resident window
    explicit = algo.predict(model, Query(user="u1", num=3,
                                         recent_items=("i4", "i9")))
    resident = algo.predict(model, Query(user="u1", num=3))
    assert explicit != resident and len(explicit.item_scores) == 3
    assert algo.predict(model, Query(user="nobody", num=3)).item_scores == ()
    # a store written since the model was prepared: nothing is resident
    monkeypatch.setattr(algo, "_store_version", lambda: 41)
    assert algo.batch_serve_json(model, docs) is None
    moved = algo.batch_predict(model, [(0, Query(user="u1", num=3))])
    assert moved[0][1].item_scores == ()      # the live history is empty
    # a model no prepare_model has seen serves nothing from residence
    monkeypatch.undo()
    fresh = SeqRecModel(**{f: getattr(model, f) for f in (
        "weights", "item_bimap", "n_heads", "max_len", "final_loss", "spec",
        "windows", "user_bimap")})
    assert algo.batch_serve_json(fresh, docs) is None


def test_a_warm_ladder_serves_every_rung_without_a_compile(served):
    algo, model = served
    algo.warmup(model, max_batch=8)
    warm = topk.serve_compile_cache_size()
    # the explicit-history program and the eight widths a batch of up to
    # eight runs at: a padded row would be a whole forward
    assert warm >= 9
    for size in (1, 2, 3, 4, 5, 7, 8):
        docs = [{"user": f"u{(3 * i) % N_USERS}", "num": 10}
                for i in range(size)]
        assert all(algo.batch_serve_json(model, docs))
    algo.predict(model, Query(user="x", num=10, recent_items=("i2",)))
    assert topk.serve_compile_cache_size() == warm


def test_counters_are_booked_once_a_dispatch(served):
    from incubator_predictionio_tpu.obs import metrics

    algo, model = served

    def read():
        tokens = metrics.REGISTRY.get("pio_seq_tokens_total").value
        pads = metrics.REGISTRY.get("pio_seq_pad_tokens_total").value
        experts = metrics.REGISTRY.get("pio_seq_moe_expert_tokens_total")
        return tokens, pads, sum(
            experts.labels(expert=e).value for e in range(8))

    t0, p0, e0 = read()
    # three queries run at a width of three; user 0's window has five PADs
    algo.batch_serve_json(model, [{"user": u, "num": 10}
                                  for u in ("u0", "u5", "u6")])
    t1, p1, e1 = read()
    assert t1 - t0 == 3 * LENGTH
    assert p1 - p0 == 5
    assert e1 - e0 == 3 * LENGTH * 2 * 4      # rows × top-2 × four layers


def test_book_is_a_phase_and_the_phases_still_tile_the_thread(
        served, monkeypatch):
    """``serve.book`` (unpacking the fetched array, booking the counters
    above) is a phase of its own, once a dispatch, and a dispatcher
    thread's phases still sum to its life: the seconds leave ``other``,
    none are added."""
    from incubator_predictionio_tpu.obs import trace as obs_trace
    from incubator_predictionio_tpu.serving.scheduler import BatchScheduler

    algo, model = served
    booked = []

    class Child:
        def __init__(self, phase):
            self.phase = phase

        def inc(self, n):
            booked.append((self.phase, n))

    class Children(dict):
        def get(self, name, default=None):
            if name not in self:
                self[name] = Child("other" if name == "serve.dispatch"
                                   else name.rpartition(".")[2])
            return self[name]

    class Clock:
        now = 0.0

        def perf_counter(self):
            return self.now

        def __getattr__(self, name):
            return getattr(__import__("time"), name)

    clock = Clock()
    monkeypatch.setattr(obs_trace, "_phase_children", Children())
    monkeypatch.setattr(obs_trace, "time", clock)
    inner = SeqRecAlgorithm._book

    def book(*args):
        clock.now += 0.003
        inner(*args)

    monkeypatch.setattr(SeqRecAlgorithm, "_book", staticmethod(book))
    s = BatchScheduler(lambda bodies: algo.batch_serve_json(model, bodies),
                       4, shed=False, wait_bound_s=0.0)
    thread = s._threads[0]
    futs = [s.submit({"user": f"u{n}", "num": 10}) for n in range(5)]
    assert all(f.result(60) is not None for f in futs)
    s.stop()
    thread.join(10)
    assert not thread.is_alive()
    by_phase = {}
    for phase, sec in booked:
        by_phase.setdefault(phase, []).append(sec)
    assert {"wait", "other", "lookup", "launch", "fetch", "book",
            "render", "complete"} <= set(by_phase)
    dispatches = len(by_phase["other"])
    assert len(by_phase["book"]) == dispatches
    assert sum(by_phase["book"]) == pytest.approx(0.003 * dispatches)
    assert sum(by_phase["other"]) == pytest.approx(0.0)
    # every second of the thread's life is in one phase
    assert sum(sec for _p, sec in booked) == pytest.approx(clock.now)


def test_multiplied_rows_are_booked_beside_the_routed(served, monkeypatch):
    """Off a TPU three ``ragged_dot`` multiply the routed rows and nothing
    else; at a width the TPU kernel serves, every block a group touches."""
    from incubator_predictionio_tpu.obs import metrics
    from incubator_predictionio_tpu.ops import pallas_kernels

    algo, model = served
    multiplied = metrics.REGISTRY.get("pio_seq_moe_rows_multiplied_total")
    before = multiplied.value
    algo.batch_serve_json(model, [{"user": u, "num": 10}
                                  for u in ("u0", "u5", "u6")])
    assert multiplied.value - before == 3 * LENGTH * 2 * 4
    # two layers of 512 rows over four experts, as a TPU would run them
    monkeypatch.setattr(pallas_kernels, "pallas_available", lambda: True)
    routed = np.asarray([[100, 300, 0, 112], [128, 128, 128, 128]])
    rows = moe.kernel_rows(512, 4)
    before = multiplied.value
    SeqRecAlgorithm._book(1, 1, 64, np.zeros(1, np.int64), routed,
                          "bfloat16")
    assert multiplied.value - before == sum(
        moe.rows_multiplied(layer, rows) for layer in routed)
    assert moe.rows_multiplied(routed[0], rows) > 512
    # float32 rows take the grouped products there too
    before = multiplied.value
    SeqRecAlgorithm._book(1, 1, 64, np.zeros(1, np.int64), routed,
                          "float32")
    assert multiplied.value - before == 1024


@pytest.mark.parametrize("cap, widths", [
    (1, (1,)), (2, (1, 2)), (3, (1, 2, 3, 4)), (8, tuple(range(1, 9))),
    (32, tuple(range(1, 9)) + (16, 32)),
])
def test_a_batch_of_up_to_eight_runs_at_its_own_width(cap, widths):
    """A padded row of this program is a whole forward, so the widths up
    to eight are each a program; past eight the factor engines' ladder."""
    assert SeqRecAlgorithm._widths(cap) == widths
    for n in range(1, cap + 1):
        assert SeqRecAlgorithm._width(n) in widths
        assert n <= SeqRecAlgorithm._width(n) < 2 * n


def test_trained_sasrec_serves_its_users_from_residence():
    from incubator_predictionio_tpu.models.sequence.engine import (
        PreparedData,
    )
    from incubator_predictionio_tpu.parallel.context import RuntimeContext

    seqs = np.array([[0, 1, 2, 3, 4], [2, 3, 4, 5, 1], [0, 0, 4, 5, 3]],
                    np.int32)
    algo = SeqRecAlgorithm(SeqRecAlgorithmParams(
        app_name=None, d_model=8, n_heads=2, n_layers=1, epochs=1))
    pd = PreparedData(sequences=seqs,
                      item_bimap=BiMap({f"i{k}": k for k in range(6)}),
                      user_bimap=BiMap({"a": 0, "b": 1, "c": 2}))
    model = algo.prepare_model(None, algo.train(RuntimeContext(), pd))
    assert model.windows.shape == (3, 4)
    docs = [{"user": "b", "num": 2}, {"user": "c", "num": 3}]
    got = algo.batch_serve_json(model, docs)
    assert got == object_bytes(algo, model, docs)
    # user c saw items 4, 5, 3 (tokens) = i3, i4, i2
    assert not {r["item"] for r in json.loads(got[1])["itemScores"]} & {
        "i3", "i4", "i2"}
    with pytest.raises(NotImplementedError):
        SeqRecAlgorithm(SeqRecAlgorithmParams(
            app_name=None, block=json_codec.to_jsonable(TINY))).train(
                RuntimeContext(), pd)


def test_the_two_copies_of_the_reference_agree(weights):
    from benchmark.reference import mellum2_topk

    sz = reference.sizes_of(TINY, VOCAB)
    layers = reference.layers_of(TINY, weights)
    window = windows_of(2, seed=5)[1]
    ours = reference.forward_hidden(sz, weights.item_emb, weights.lnf_scale,
                                    layers, window)
    theirs = mellum2_topk.forward_hidden(sz, weights.item_emb,
                                         weights.lnf_scale, layers, window)
    assert np.array_equal(np.asarray(ours), np.asarray(theirs))
    assert np.array_equal(
        np.asarray(reference.head_logits(ours, weights.head)),
        np.asarray(mellum2_topk.head_logits(theirs, weights.head)))
    for rope in sz["rope"].values():
        assert np.array_equal(reference.inv_freq(rope, 16),
                              mellum2_topk.inv_freq(rope, 16))


def test_the_compile_cache_collector_survives_a_module_half_imported(
        monkeypatch):
    """`/metrics` may be scraped while another thread still imports
    ops/topk: the module is in ``sys.modules`` and has no
    ``serve_compile_cache_size`` yet."""
    import sys
    import types

    from incubator_predictionio_tpu.serving import scheduler

    scheduler._COMPILE_CACHE.set(7.0)
    monkeypatch.setitem(sys.modules, "incubator_predictionio_tpu.ops.topk",
                        types.ModuleType("half_imported"))
    scheduler._collect_compile_cache()        # must not raise
    assert scheduler._COMPILE_CACHE.value == 7.0
    monkeypatch.undo()
    scheduler._collect_compile_cache()
    assert scheduler._COMPILE_CACHE.value == float(
        topk.serve_compile_cache_size())


def test_the_shared_renderer_mirrors_json_dumps():
    """utils/item_scores.render_item_scores, the one hand-mirrored format
    of both engines' fast paths: json.dumps' bytes, masked slots left
    out, further fields after the score, None for a score json cannot
    say."""
    from incubator_predictionio_tpu.utils.item_scores import (
        render_item_scores,
    )

    top_s = np.array([2.5, 1.0e-7, -3e38, 0.25], np.float32)
    top_i = np.array([3, 1, 0, 2])
    names = ["a", 'b"é', "c", "d"]
    got = render_item_scores(top_s, top_i, 3, names.__getitem__)
    want = json.dumps({"itemScores": [
        {"item": "d", "score": float(top_s[0])},
        {"item": 'b"é', "score": float(top_s[1])}]})
    assert got == want.encode("utf-8")
    more = render_item_scores(top_s, top_i, 1, names.__getitem__,
                              lambda iid: ', "creationYear": null')
    assert json.loads(more) == {"itemScores": [
        {"item": "d", "score": 2.5, "creationYear": None}]}
    assert render_item_scores(np.array([np.inf]), np.array([0]), 1,
                              names.__getitem__) is None
    assert render_item_scores(top_s[2:3], top_i[2:3], 1,
                              names.__getitem__) == b'{"itemScores": []}'
