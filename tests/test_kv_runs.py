"""Pages in runs: the allocator lays a lane's own pages out in aligned runs
of 8 blocks (serving/kv_cache.py:BlockAllocator), and the fused decode
kernel fetches a group of 8 table entries whose block ids are consecutive as
ONE copy an array (ops/pallas_attention.py:_fused_decode_kernel).

  * the allocator: runs aligned, block 0 never handed out, ``extend`` inside
    a run takes nothing, whole runs again after churn, single blocks only
    where no whole run is free (a run partly pinned by a prefix entry among
    them), ``can_alloc`` agreeing with ``alloc``;
  * the kernel, in the Pallas interpreter, on tables the allocator made
    (all runs, a shared prefix before a lane's own runs and the group that
    straddles them, inactive lanes, a last partial group): bit-equal to the
    same pool with every block id moved so that no group is a run (the
    per-page path), and within the float32 oracle's tolerance of the gather
    path, at Qwen2-7B's heads (28 / 4 x 128, rope) and the jamba cell's
    (20 / 1 x 128, no rope, 256 lanes);
  * the host's count of run groups (``fetch_groups``, behind ``engine.call``
    ``kv_run_groups``) against a plain loop, with the kernel's predicate.
"""

import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_llm_monitor_tpu.ops import pallas_attention as pa
from k8s_llm_monitor_tpu.ops.attention import paged_decode_attention
from k8s_llm_monitor_tpu.ops.rope import apply_rope, rope_angles
from k8s_llm_monitor_tpu.resilience.tenancy import DEFAULT_TENANT as TEN
from k8s_llm_monitor_tpu.serving.kv_cache import (
    RUN_BLOCKS,
    BlockAllocator,
    OutOfBlocks,
    PrefixCache,
)

R = RUN_BLOCKS
BS = 16


def _is_run(blocks) -> bool:
    return (len(blocks) == R and blocks[0] % R == 0
            and list(blocks) == list(range(blocks[0], blocks[0] + R)))


def _groups(table_row, start=0):
    """The whole groups of a lane's table from position ``start`` on."""
    first = -(-start // R) * R
    return [table_row[g:g + R] for g in range(first, len(table_row) - R + 1, R)]


# ---------------------------------------------------------------------------
# The allocator
# ---------------------------------------------------------------------------


def test_run_blocks_is_the_kernels_fetch_group():
    assert RUN_BLOCKS == pa.FUSED_GROUP == 8


def test_runs_are_aligned_and_block_zero_is_never_handed_out():
    a = BlockAllocator(num_blocks=10 * R + 3, block_size=BS, table_blocks=32)
    lane = a.alloc(20 * BS, at=0)               # 20 blocks -> three runs
    assert len(lane) == 3 * R and all(map(_is_run, _groups(lane)))
    assert a.free_blocks == 10 * R + 2 - 3 * R
    handed = list(lane)
    while True:
        try:
            handed += a.alloc(5 * BS, at=0)
        except OutOfBlocks:
            break
    assert 0 not in handed and len(set(handed)) == len(handed)
    assert a.free_blocks < 5 and sorted(handed + list(a._singles)) == list(
        range(1, 10 * R + 3))


def test_extend_inside_a_run_takes_nothing():
    a = BlockAllocator(num_blocks=8 * R, block_size=BS, table_blocks=32)
    free0 = a.free_blocks
    lane = a.alloc(1, at=0)                     # one token: a whole run
    assert _is_run(lane) and a.free_blocks == free0 - R
    for tokens in (2, BS, BS + 1, R * BS):
        a.extend(lane, tokens)
        assert len(lane) == R and a.free_blocks == free0 - R
    a.extend(lane, R * BS + 1)                  # crosses into the next group
    assert len(lane) == 2 * R and all(map(_is_run, _groups(lane)))
    a.free(lane)
    assert a.free_blocks == free0 and len(a._runs) == free0 // R


def test_the_table_width_caps_the_rounding():
    a = BlockAllocator(num_blocks=8 * R, block_size=BS, table_blocks=12)
    lane = a.alloc(9 * BS, at=0)                # 9 blocks, rounded to 12
    assert len(lane) == 12 and _is_run(lane[:R])
    a.extend(lane, 12 * BS)
    assert len(lane) == 12
    exact = a.alloc(9 * BS)                     # a prefix entry's span
    assert len(exact) == 9 and _is_run(exact[:R])


def test_a_shared_prefix_is_followed_by_aligned_runs():
    """A lane behind 3 shared blocks takes the rest of that group a block at
    a time (it straddles the prefix and cannot be a run) and whole runs for
    the groups after it; a part that ends inside the straddling group takes
    exactly what it needs."""
    a = BlockAllocator(num_blocks=16 * R, block_size=BS, table_blocks=48)
    owner = a.alloc(3 * BS + 1, at=0)
    shared = owner[:3]
    a.incref(shared)
    lane = shared + a.alloc(20 * BS, at=3)      # positions 3 .. 22 -> 24
    assert len(lane) == 24 and all(map(_is_run, _groups(lane, 3)))
    a.incref(shared)
    short = shared + a.alloc(2 * BS, at=3)
    assert len(short) == 5


def test_new_lanes_get_whole_runs_after_churn():
    """Lanes of random sizes admitted, grown and retired in a seeded random
    order: while whole runs are free every whole group of a lane is one, and
    once everything is retired every run is whole again."""
    rng = np.random.default_rng(38)
    a = BlockAllocator(num_blocks=40 * R + 5, block_size=BS, table_blocks=64)
    lanes: list[list[int]] = []
    for _ in range(400):
        if lanes and (rng.random() < 0.4 or a.free_blocks < 3 * R):
            a.free(lanes.pop(int(rng.integers(len(lanes)))))
            continue
        if lanes and rng.random() < 0.5:
            lane = lanes[int(rng.integers(len(lanes)))]
            target = min(len(lane) * BS + int(rng.integers(1, 3 * R * BS)),
                         64 * BS)
            if -(-a.blocks_for(target) // R) - len(lane) // R > len(a._runs):
                continue
            a.extend(lane, target)
            assert len(lane) % R == 0 and all(map(_is_run, _groups(lane)))
            continue
        tokens = int(rng.integers(1, 20 * BS))
        whole = -(-a.blocks_for(tokens) // R)
        if whole > len(a._runs):
            continue
        lanes.append(a.alloc(tokens, at=0))
        assert all(map(_is_run, _groups(lanes[-1])))
    for lane in lanes:
        a.free(lane)
    assert a.free_blocks == 40 * R + 4 and len(a._runs) == 39
    lane = a.alloc(60 * BS, at=0)
    assert len(lane) == 64 and all(map(_is_run, _groups(lane)))


def test_single_blocks_only_when_no_whole_run_is_free():
    """A prefix entry outlives its lane and pins 3 blocks of a run: the other
    5 are handed out singly, and only after every whole run is gone."""
    a = BlockAllocator(num_blocks=4 * R, block_size=BS, table_blocks=64)
    pc = PrefixCache(a, max_entries=4)
    prompt = list(range(3 * BS + 2))
    lane = a.alloc(len(prompt) + 1, at=0)
    pinned_run = lane[0] // R
    pc.register(prompt, lane, tenant=TEN)       # pins lane[:3]
    a.free(lane)
    assert len(a._runs) == 2 and a.free_blocks == 4 * R - 1 - 3
    got = [a.alloc(1, at=0) for _ in range(2)]
    assert all(map(_is_run, got))
    assert all(b // R != pinned_run for lane in got for b in lane)
    assert not a._runs
    singles = a.alloc(R * BS, at=0)             # no whole run: singles
    assert len(singles) == R and not _is_run(singles)
    assert {b // R for b in singles} <= {0, pinned_run}


@pytest.mark.parametrize("seed", range(4))
def test_can_alloc_agrees_with_alloc(seed):
    """Whatever the pool holds, ``can_alloc(tokens)`` is True exactly when
    ``alloc(tokens, at=...)`` succeeds, and ``alloc`` takes more than the
    tokens need only where the more fits."""
    rng = np.random.default_rng(seed)
    a = BlockAllocator(num_blocks=12 * R + 3, block_size=BS, table_blocks=40)
    lanes = []
    for _ in range(60):
        tokens = int(rng.integers(1, 30 * BS))
        at = int(rng.choice([0, 0, 3, 8]))
        trial = copy.deepcopy(a)
        try:
            got = trial.alloc(tokens, at=at)
            ok = True
        except OutOfBlocks:
            ok = False
        assert a.can_alloc(tokens) == ok
        if ok:
            need = a.blocks_for(tokens)
            assert need <= len(got) <= max(need, a.free_blocks)
            assert at + len(got) <= max(40, at + need)
            lanes.append(a.alloc(tokens, at=at))
        if lanes and rng.random() < 0.3:
            a.free(lanes.pop(int(rng.integers(len(lanes)))))


# ---------------------------------------------------------------------------
# The kernel, on tables the allocator made
# ---------------------------------------------------------------------------


def _allocator_tables(B, NB, positions, shared_every=0):
    """Block tables from the allocator: lane b holds ``positions[b] + 1``
    tokens (0: inactive, a zero row); with ``shared_every``, every such lane
    reads the first 3 blocks of lane 0 (a shared prefix: the group it
    straddles is fetched page by page)."""
    need = sum(min(-(-(-(-(int(p) + 1) // BS)) // R) * R, NB)
               for p in positions if p)
    a = BlockAllocator(num_blocks=need + 3 * R, block_size=BS,
                       table_blocks=NB)
    table = np.zeros((B, NB), np.int32)
    for b, p in enumerate(positions):
        if p == 0:
            continue
        if shared_every and b % shared_every == 1 and table[0, 2]:
            shared = [int(x) for x in table[0, :3]]
            a.incref(shared)
            row = shared + a.alloc(int(p) + 1 - 3 * BS, at=3)
        else:
            row = a.alloc(int(p) + 1, at=0)
        table[b, :len(row)] = row
    return table, a.num_blocks


def _plain_count(table, cached, G=R):
    """fetch_groups as a loop over steps and lanes: groups of a lane's
    cached pages, and those of G consecutive ids (past the table, its last
    entry repeated)."""
    groups = runs = 0
    for step in np.atleast_2d(cached):
        for row, p in zip(table, step):
            n = -(-(-(-int(p) // BS)) // G)
            padded = list(row) + [row[-1]] * G
            groups += n
            runs += sum(all(padded[g * G + i] == padded[g * G] + i
                            for i in range(G)) for g in range(n))
    return groups, runs


_KERNEL = jax.jit(functools.partial(pa.paged_decode_attention_fused,
                                    interpret=True))


def _case(B, H, KVH, D, NB, positions, rope, seed, shared_every=0):
    rng = np.random.default_rng(seed)
    table, num_blocks = _allocator_tables(B, NB, positions, shared_every)
    bf = jnp.bfloat16

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32), bf)

    q, kn, vn = normal(B, 1, H, D), normal(B, 1, KVH, D), normal(B, 1, KVH, D)
    kp, vp = normal(num_blocks, BS, KVH * D), normal(num_blocks, BS, KVH * D)
    pos = jnp.asarray(positions, jnp.int32)
    cos, sin = rope_angles(pos[:, None], D, 10_000.0)
    if not rope:
        cos, sin = jnp.ones_like(cos), jnp.zeros_like(sin)
    return table, (q, kn, vn, cos, sin, kp, vp), pos


def _scattered(table, pages):
    """The same pool with block b moved to ``num_blocks - b`` (0 stays):
    consecutive ids become descending ones, so no group is a run."""
    n = pages[0].shape[0]
    move = np.concatenate([[0], n - np.arange(1, n)]).astype(np.int32)
    inverse = np.argsort(move)
    return move[table], [p[inverse] for p in pages], move


def _gather_oracle(args, table, pos, rope):
    q, kn, vn, cos, sin, kp, vp = (x.astype(jnp.float32)
                                   if x.dtype == jnp.bfloat16 else x
                                   for x in args)
    from k8s_llm_monitor_tpu.models import llama

    active = (pos > 0)[:, None]
    if rope:
        q, kn = apply_rope(q, cos, sin), apply_rope(kn, cos, sin)
    # The gather reads the table's whole width: keep the columns in use.
    t = jnp.asarray(table[:, :-(-(int(pos.max()) + 1) // BS)])
    pk = llama._scatter_pages(kp, kn, t, pos[:, None], active)
    pv = llama._scatter_pages(vp, vn, t, pos[:, None], active)
    return paged_decode_attention(q, pk, pv, t, pos + 1)


@pytest.mark.parametrize("shape", [
    # Qwen2-7B: 28 q / 4 kv x 128, rope; inactive lanes, a shared prefix
    # and the group straddling it, last partial groups, three windows.
    dict(B=16, H=28, KVH=4, D=128, NB=96, rope=True, shared_every=4,
         positions=[700, 0, 1, 9 * BS, 1535, 128, 129, 0,
                    40, 513, 8 * BS - 1, 8 * BS, 300, 0, 1000, 77]),
    # The jamba cell: 20 q / 1 kv x 128, no rope, 256 lanes.
    dict(B=256, H=20, KVH=1, D=128, NB=256, rope=False, shared_every=0,
         positions=None),
], ids=["qwen2", "jamba"])
def test_runs_are_bit_equal_to_pages_and_match_the_gather_oracle(shape):
    shape = dict(shape)
    B, NB, rope = shape["B"], shape["NB"], shape["rope"]
    positions = shape.pop("positions")
    if positions is None:
        rng = np.random.default_rng(256)
        positions = rng.integers(1, 3 * R * BS, size=B)
        positions[rng.random(B) < 0.1] = 0
    positions = np.asarray(positions)
    table, args, pos = _case(B, shape["H"], shape["KVH"], shape["D"], NB,
                             positions, rope, seed=B,
                             shared_every=shape["shared_every"])
    groups, runs = pa.fetch_groups(table, positions, BS)
    assert (groups, runs) == _plain_count(table, positions)
    assert runs >= 0.8 * groups > 0             # mostly runs
    if shape["shared_every"]:
        assert runs < groups                    # and some page by page

    got, gk, gv = _KERNEL(*args, jnp.asarray(table), pos)
    s_table, s_pages, move = _scattered(table, args[5:])
    assert pa.fetch_groups(s_table, positions, BS) == (groups, 0)
    s_got, s_k, s_v = _KERNEL(*args[:5], *s_pages, jnp.asarray(s_table), pos)
    # Only the copies differ: the same bytes reach the same VMEM rows.
    assert np.array_equal(np.asarray(got), np.asarray(s_got))
    assert np.array_equal(np.asarray(gk)[1:], np.asarray(s_k)[move][1:])
    assert np.array_equal(np.asarray(gv)[1:], np.asarray(s_v)[move][1:])

    want = _gather_oracle(args, table, pos, rope)
    live = positions > 0
    np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                               np.asarray(want, np.float32)[live],
                               rtol=2e-2, atol=2e-2)


def test_the_kernel_and_the_host_count_share_one_predicate(monkeypatch):
    """The kernel asks ``group_is_run`` for every fetch group it lowers, at
    the group size ``fetch_groups`` counts with."""
    seen = []
    real = pa.group_is_run

    def spy(entry, G):
        seen.append(G)
        return real(entry, G)

    monkeypatch.setattr(pa, "group_is_run", spy)
    B, H, KVH, D, NB = 2, 4, 2, 64, 40
    positions = np.asarray([17, 300])
    table, args, pos = _case(B, H, KVH, D, NB, positions, True, seed=1)
    jax.make_jaxpr(functools.partial(pa.paged_decode_attention_fused,
                                     interpret=True))(
        *args, jnp.asarray(table), pos)
    assert seen and set(seen) == {pa._fetch_group(NB)} == {R}


@pytest.mark.parametrize("NB,steps", [(96, 1), (36, 8), (12, 3)],
                         ids=["qwen2-table", "table-past-a-window", "narrow"])
def test_fetch_groups_counts_what_a_call_streams(NB, steps):
    """Over a call's steps (lane i at ``ctx + s`` tokens), for a table that
    is a whole number of windows, one that is not (the kernel repeats its
    last entry past it) and one narrower than a group."""
    rng = np.random.default_rng(NB)
    B = 8
    ctx = rng.integers(0, NB * BS - steps, size=B)
    ctx[2] = 0
    table, _ = _allocator_tables(B, NB, ctx + steps)
    cached = np.where(ctx > 0, ctx + np.arange(steps)[:, None], 0)
    groups, runs = pa.fetch_groups(table, cached, BS)
    G = pa._fetch_group(NB)
    assert (groups, runs) == _plain_count(table, cached, G)
    if G == R:
        assert runs > 0


def test_decode_calls_count_their_groups_and_the_exporter_says_so():
    """An engine whose lanes take whole runs: every decode call's span
    carries ``kv_groups`` / ``kv_run_groups``, each group of a lane's own
    pages is a run, and the counter twin sums them."""
    from k8s_llm_monitor_tpu.models import llama
    from k8s_llm_monitor_tpu.models.config import PRESETS
    from k8s_llm_monitor_tpu.monitor import exporter
    from k8s_llm_monitor_tpu.observability.tracing import (
        Tracer,
        get_tracer,
        set_tracer,
    )
    from k8s_llm_monitor_tpu.serving.engine import (
        SPAN_CATALOG,
        EngineConfig,
        InferenceEngine,
        SamplingParams,
    )

    cfg = PRESETS["tiny-qwen"]
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(3, cfg.vocab_size, size=n))
               for n in (9, 30, 17, 41)]
    before = get_tracer()
    set_tracer(Tracer(ring_size=4096, sample=1.0))
    try:
        eng = InferenceEngine(cfg, params, EngineConfig(
            max_slots=4, num_blocks=12 * R, block_size=4,
            max_blocks_per_seq=16, prefill_buckets=(64,)), eos_id=-1)
        eng.generate(prompts, SamplingParams(max_tokens=12))
        calls = [s["attrs"] for s in get_tracer().snapshot()
                 if s["name"] == "engine.call" and s["attrs"]["kind"] == "decode"]
    finally:
        set_tracer(before)
    assert calls and all(set(c) <= set(SPAN_CATALOG["engine.call"])
                         for c in calls)
    groups = sum(c["kv_groups"] for c in calls)
    runs = sum(c["kv_run_groups"] for c in calls)
    assert groups > 0 and runs == groups
    assert eng.kv_page_groups == {"run": runs, "pages": 0}
    w = exporter._Writer()
    exporter._loop_metrics(w, eng)
    assert (f'k8s_llm_monitor_engine_kv_page_groups_total{{form="run"}} '
            f'{runs}') in "\n".join(w.lines)
