"""The expert layer's grouped product (ops/grouped.py): the stream kernel, in
the interpreter, is ``jax.lax.ragged_dot`` bit for bit on every row a group
covers; the tiles kernel is ``ragged_dot`` and the dequantisation that follows
it bit for bit; and the predicate sends a decode step's shapes to the first,
an admission call's to the second and everything else to the compiler's
product.
"""

import json
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_monitor_tpu.models.config import PRESETS
from k8s_llm_monitor_tpu.ops import grouped

NEMOTRON = PRESETS["nemotron3-super-120b-a12b-22l"]
KANANA = PRESETS["kanana-2-30b-a3b-12l"]
DOTS3 = PRESETS["dots3-note-prev-5l"]
ROOT = pathlib.Path(__file__).resolve().parents[1]


def _even(rng, live: int, groups: int):
    return rng.multinomial(live, np.ones(groups) / groups)


# name -> (M, K, N, group sizes): the cells' decode shapes (64 lanes x experts
# per token sorted rows, 128 experts; the nemotron chip holds a quarter of
# the assignments) cut in K and N for speed, and the cases a schedule of
# (row tile, group) visits can get wrong.
def _cases():
    rng = np.random.default_rng(32)
    return {
        "nemotron-decode-up": (1408, 256, 384, _even(rng, 352, 128)),
        "nemotron-decode-down": (1408, 384, 256, _even(rng, 352, 128)),
        "kanana-decode-up": (384, 256, 128, _even(rng, 384, 128)),
        "kanana-decode-down": (384, 128, 256, _even(rng, 384, 128)),
        "kanana-decode-up-full-width": (384, 2048, 768, _even(rng, 384, 128)),
        "empty-groups": (96, 128, 128, [0, 5, 0, 0, 40, 0, 3, 0]),
        "a-group-of-one-row": (64, 128, 128, [1, 0, 1, 30, 1]),
        "a-group-over-three-tiles": (128, 128, 256, [20, 70, 5, 33]),
        "all-rows-in-one-group": (96, 128, 128, [0, 0, 96, 0]),
        "rows-behind-the-last-group": (160, 128, 128, [3, 40, 0, 7]),
        "no-row-at-all": (64, 128, 128, [0, 0, 0]),
        "one-group": (64, 128, 128, [50]),
        "rows-not-a-multiple-of-the-tile": (50, 64, 96, [1, 2, 3, 0, 10, 30]),
    }


CASES = _cases()


@pytest.mark.parametrize("name", list(CASES))
def test_stream_kernel_is_ragged_dot_bit_for_bit(name):
    M, K, N, sizes = CASES[name]
    rng = np.random.default_rng(len(name))
    G, live = len(sizes), int(np.sum(sizes))
    rows = jnp.asarray(rng.integers(-127, 128, (M, K), dtype=np.int8))
    kernels = jnp.asarray(rng.integers(-127, 128, (G, K, N), dtype=np.int8))
    sizes = jnp.asarray(sizes, jnp.int32)
    want = grouped.grouped_rows_product_xla(rows, kernels, sizes)
    got = grouped.grouped_rows_product(rows, kernels, sizes, interpret=True)
    assert got.shape == (M, N) and got.dtype == jnp.int32
    # Rows behind the last group may hold anything: they are not compared.
    assert np.array_equal(np.asarray(got)[:live], np.asarray(want)[:live])


# name -> (M, K, N, group sizes, row tile or None for ``TILES_ROWS``): the
# cells' admission shapes cut in rows, groups and widths (nemotron: a window
# of which ~5/8 are live, 128 rows an expert; kanana: 768 rows an expert;
# dots3: a window whose live rows lie on half the experts, 256 rows an
# expert), two at full width (dots3's [5120, 1536] splits its columns), and
# what a schedule can get wrong, at a row tile of 128 and of 256.
def _tiles_cases():
    rng = np.random.default_rng(34)
    uneven = lambda live, groups: rng.multinomial(  # noqa: E731
        live, rng.dirichlet(np.ones(groups) * 2))
    half = np.zeros(8, np.int64)
    half[2:6] = uneven(2048, 4)
    return {
        "nemotron-admit-up": (2048, 256, 384, uneven(1280, 16), None),
        "nemotron-admit-down": (2048, 384, 256, uneven(1280, 16), None),
        "kanana-admit-up": (3072, 256, 128, uneven(3072, 4), None),
        "kanana-admit-down": (3072, 128, 256, uneven(3072, 4), None),
        "dots3-admit-up": (2048, 640, 256, half, None),
        "dots3-admit-down": (2048, 256, 640, half, None),
        "kanana-admit-up-full-width": (640, 2048, 768, [200, 0, 317, 123], None),
        "dots3-admit-up-full-width": (256, 5120, 1536, [90, 166], None),
        "empty-groups": (384, 128, 128, [0, 140, 0, 0, 170, 0, 30, 0], 128),
        "a-tile-shared-by-three-groups": (256, 128, 256, [100, 9, 7, 140], 128),
        "a-group-over-three-tiles": (512, 128, 128, [60, 300, 152], 128),
        "rows-behind-the-last-group": (1024, 128, 128, [3, 400, 0, 70], 256),
        "rows-not-a-multiple-of-the-tile": (300, 64, 96, [1, 2, 130, 0, 160], 128),
        "no-row-at-all": (256, 128, 128, [0, 0, 0], None),
        "one-group": (256, 128, 128, [200], None),
    }


TILES_CASES = _tiles_cases()


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("name", list(TILES_CASES))
def test_tiles_kernel_is_ragged_dot_and_its_dequantisation_bit_for_bit(
        name, dtype):
    """The tiles form against what it replaces: ``_expert_rows`` itself,
    which on the CPU is ``ragged_dot`` and the XLA dequantisation."""
    from k8s_llm_monitor_tpu.models import llama

    M, K, N, sizes, tile_rows = TILES_CASES[name]
    rng = np.random.default_rng(len(name))
    G, live = len(sizes), int(np.sum(sizes))
    rows = jnp.asarray(rng.integers(-127, 128, (M, K), dtype=np.int8))
    p = {"kernel_q": jnp.asarray(rng.integers(-127, 128, (G, K, N),
                                              dtype=np.int8)),
         "scale": jnp.asarray(rng.random((G, N), dtype=np.float32) / 127)}
    row_scale = jnp.asarray(rng.random((M, 1), dtype=np.float32) * 3)
    rows_e = jnp.asarray(np.minimum(
        np.repeat(np.arange(G + 1), [*sizes, M - live]), G - 1), jnp.int32)
    sizes = jnp.asarray(sizes, jnp.int32)
    want = llama._expert_rows(p, rows, row_scale, rows_e, sizes,
                              jnp.dtype(dtype))
    got = grouped.grouped_tiles_product(
        rows, p["kernel_q"], sizes, row_scale, p["scale"],
        dtype=jnp.dtype(dtype), tile_rows=tile_rows, interpret=True)
    assert got.shape == (M, N) and got.dtype == want.dtype == jnp.dtype(dtype)
    # Rows behind the last group may hold anything: they are not compared.
    bits = {"bfloat16": np.uint16, "float32": np.uint32}[dtype]
    assert np.array_equal(np.asarray(got)[:live].view(bits),
                          np.asarray(want)[:live].view(bits))


@pytest.mark.parametrize("kernel,tile", [
    # [k, n] of an expert's kernel: nemotron's, dots3's in both orientations
    # (two column tiles), kanana's in both.
    ((1024, 2688), 2688), ((5120, 1536), 768), ((1536, 5120), 2560),
    ((2048, 768), 768), ((768, 2048), 2048)])
def test_the_column_tile_bounds_a_kernel_block(kernel, tile):
    k, n = kernel
    tn = grouped.column_tile(k, n)
    assert tn == tile and n % tn == 0 and tn % 128 == 0
    assert k * tn <= grouped.TILE_KERNEL_BYTES
    assert grouped.TILES_ROWS % 32 == 0


def test_the_schedule_visits_each_hit_group_once_a_tile_and_no_other():
    sizes = jnp.asarray([0, 5, 0, 40, 0, 3, 0, 0], jnp.int32)
    group, tile, offsets, used = grouped._visits(sizes, tiles=3, tm=32)
    used = int(used[0])
    # 5 rows in tile 0; 40 rows over tiles 0-1; 3 rows in tile 1.
    assert used == 4
    assert list(zip(np.asarray(group)[:used], np.asarray(tile)[:used])) == [
        (1, 0), (3, 0), (3, 1), (5, 1)]
    # The unused tail repeats the last visit: its steps fetch nothing.
    assert set(np.asarray(group)[used:]) == {5}
    assert set(np.asarray(tile)[used:]) == {1}
    assert list(np.asarray(offsets)) == [0, 0, 5, 5, 45, 45, 48, 48, 48]


def _product_shapes(cfg):
    """(K, N) of an expert's two kinds of product."""
    narrow, wide = cfg.moe_latent_size or cfg.hidden_size, cfg.expert_width
    return (narrow, wide), (wide, narrow)


@pytest.mark.parametrize("cfg,tokens,form", [
    (NEMOTRON, 64, "stream"), (KANANA, 64, "stream"), (DOTS3, 64, "stream"),
    # Admission: the smallest and the largest call of each cell (rungs
    # 384-12,288, 1,024-16,384 and 2,304-18,432 tokens; a window of the
    # share-aware layer holds at most 16,384 sorted rows).
    (NEMOTRON, 384, "tiles"), (NEMOTRON, 12_288, "tiles"),
    (KANANA, 1_024, "tiles"), (KANANA, 16_384, "tiles"),
    (DOTS3, 2_304, "tiles"), (DOTS3, 18_432, "tiles")],
    ids=["nemotron-decode", "kanana-decode", "dots3-decode",
         "nemotron-admit-384", "nemotron-admit-12288", "kanana-admit-1024",
         "kanana-admit-16384", "dots3-admit-2304", "dots3-admit-18432"])
def test_the_predicate_streams_decode_and_leaves_admission(cfg, tokens, form):
    """Three forms by the call's static shapes: every decode program of the
    three routed cells streams, every admission program takes the tiles
    form, and off the TPU both are the compiler's."""
    from k8s_llm_monitor_tpu.models import llama

    rows = tokens * cfg.num_experts_per_tok
    if cfg.expert_share:
        rows = min(rows, llama._EXPERT_WINDOW_ROWS)
    for K, N in _product_shapes(cfg):
        assert grouped.product_form(rows, cfg.experts_held_, K, N, jnp.int8,
                                    platform="tpu") == form
        assert grouped.product_form(rows, cfg.experts_held_, K, N, jnp.int8,
                                    platform="cpu") == "compiler"


@pytest.mark.parametrize("why,args,form", [
    ("off the TPU", dict(platform="cpu"), "compiler"),
    ("wide operands", dict(dtype=jnp.bfloat16), "compiler"),
    ("a width that is no multiple of 128 lanes", dict(n=96), "compiler"),
    ("many rows an expert", dict(m=128 * 128), "tiles"),
    ("many rows an expert, off the TPU", dict(m=128 * 128, platform="cpu"),
     "compiler"),
    ("many rows an expert, wide operands",
     dict(m=128 * 128, dtype=jnp.bfloat16), "compiler")])
def test_the_predicate_keeps_the_compiler_form(why, args, form):
    call = dict(m=384, g=128, k=2048, n=768, dtype=jnp.int8, platform="tpu")
    assert grouped.product_form(**call) == "stream"
    assert grouped.product_form(**{**call, **args}) == form, why
    # The int32 product of a call that does not stream is the compiler's
    # (the tiles form is no int32 product: _expert_rows asks the form first).
    assert (grouped.select_grouped_product(**{**call, **args})
            is grouped.grouped_rows_product_xla)
    assert (grouped.select_grouped_product(**call)
            is grouped.grouped_rows_product)


def test_the_compiler_form_is_ragged_dot_itself():
    """What ``_expert_rows`` lowered to before there were two forms: the
    admission programs' text does not move (tests/test_packed_prefill.py
    holds their digests)."""
    rows = jax.ShapeDtypeStruct((64, 128), jnp.int8)
    kernels = jax.ShapeDtypeStruct((4, 128, 128), jnp.int8)
    sizes = jax.ShapeDtypeStruct((4,), jnp.int32)
    before = jax.jit(lambda r, k, s: jax.lax.ragged_dot(
        r, k, s, preferred_element_type=jnp.int32)).lower(rows, kernels, sizes)
    now = jax.jit(lambda r, k, s: grouped.grouped_rows_product_xla(
        r, k, s)).lower(rows, kernels, sizes)
    assert now.as_text() == before.as_text()


# -- what the benchmark reads of it -------------------------------------------


@pytest.mark.parametrize("config,kernels_bytes", [
    ("kanana2-30b-a3b-w8a8", 3 * 2048 * 768),
    ("nemotron3-super-120b-a12b-w8a8", 2 * 1024 * 2688)])
def test_expert_counts_read_catalogued_attributes(config, kernels_bytes):
    from benchmarks.harness import expert_counts
    from k8s_llm_monitor_tpu.serving.engine import SPAN_CATALOG

    config = json.loads(
        (ROOT / f"benchmarks/configs/{config}.json").read_text())
    for name, reads in expert_counts.READS.items():
        assert set(reads) <= set(SPAN_CATALOG["engine.call"]), name
        assert getattr(expert_counts, name)(config, {}) is None
        assert name in expert_counts.PEAK_OF
    ops_, nbytes = expert_counts.expert_decode_product(
        config, {"moe_expert_layer_steps_hit": 9000.0,
                 "moe_assignments": 28000.0})
    assert nbytes == 9000 * kernels_bytes
    assert ops_ == 2 * 28000 * kernels_bytes


def test_the_roofline_metric_names_the_kernel():
    """``expert_decode_product_roofline`` finds the kernel in a device trace
    by the name its ``pallas_call`` carries (the compiled program has it
    under that name: tests/test_chip_compile.py)."""
    spec = json.loads((ROOT / "benchmarks/metrics/"
                       "expert_decode_product_roofline.json").read_text())
    assert re.search(spec["args"]["kernel"],
                     grouped.grouped_rows_product.__name__)
