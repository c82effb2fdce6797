#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

    python chip_smoke.py             one TPU chip: kernels vs oracles, then the
                                     real server over HTTP at full Qwen2-7B size
    python chip_smoke.py --chips 4   four chips: ONLY the tensor-parallel engine
                                     and the one-chip engine it is compared with

Everything runs in this one process (server, client threads, checks): a chip
belongs to one process at a time.  Weights and inputs are random, made from
``--seed``; nothing is read from outside the checkout.  Every phase that fails
ends the run with a traceback and a non-zero exit — no phase is caught and
skipped.  Without a TPU (``JAX_PLATFORMS=cpu``, or no accelerator) the script
exits 2 before any weight is built and prints no result line.

The times it prints are information about this run, not benchmark metrics.
The last line of standard output is the result object the driver reads.
"""

from __future__ import annotations

import argparse
import functools
import json
import re
import socket
import sys
import threading
import time
import urllib.request

MODEL = "qwen2-7b"

# Kernel-vs-oracle tolerance, and its reason.  Queries, pages and outputs are
# bfloat16 (8 significand bits: one rounding is up to 2**-8 = 0.4% of a
# value); both sides run the softmax in float32, but the kernels accumulate
# an online softmax window by window and rope in-kernel while the oracle
# ropes in XLA and normalizes once, so the two round differently before the
# final bfloat16 cast.  Attention outputs here are O(1), so 2e-2 absolute
# plus 2e-2 relative is about five bfloat16 roundings — tight enough that a
# wrong mask, page or head slice (errors of O(1)) cannot pass.  Quantized-KV
# checks use the same bound: kernel and oracle dequantize the same codes.
ATOL = RTOL = 2e-2


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# phase 1: every default-path kernel against its XLA oracle, at full width
# ---------------------------------------------------------------------------


def _tables(rng, np, positions, extra, bs, max_blocks, num_blocks):
    """Block tables giving each active lane distinct non-null blocks that
    cover ``position + extra`` tokens (the allocator's contract)."""
    table = np.zeros((len(positions), max_blocks), np.int32)
    order = rng.permutation(np.arange(1, num_blocks))
    nxt = 0
    for b, p in enumerate(positions):
        if p + extra[b] == 0:
            continue
        used = min((int(p) + int(extra[b]) - 1) // bs + 1, max_blocks)
        assert nxt + used <= len(order), "kernel case sized past the pool"
        table[b, :used] = order[nxt:nxt + used]
        nxt += used
    return table


def _close(np, name, got, want, rows=None):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if rows is not None:
        got, want = got[rows], want[rows]
    assert np.isfinite(got).all(), f"{name}: non-finite output"
    err = float(np.max(np.abs(got - want)))
    ok = np.allclose(got, want, atol=ATOL, rtol=RTOL)
    say(f"  kernel {name}: max|err|={err:.3e} {'ok' if ok else 'MISMATCH'}")
    assert ok, f"{name}: outside atol={ATOL} rtol={RTOL} (max|err| {err})"


def kernel_checks(cfg, seed: int, num_blocks: int, bs: int, max_blocks: int,
                  lanes: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from k8s_llm_monitor_tpu.models import llama
    from k8s_llm_monitor_tpu.ops import attention as ops
    from k8s_llm_monitor_tpu.ops import pallas_attention as pa
    from k8s_llm_monitor_tpu.ops.rope import apply_rope, rope_angles

    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    F = KVH * D
    bf16 = jnp.bfloat16
    # Compiled on the chip; the Pallas interpreter only where a rehearsal
    # imports this module on the CPU (the repo's own selection rule).
    interpret = jax.default_backend() != "tpu"

    def kernel(fn):
        return jax.jit(functools.partial(fn, interpret=interpret))
    rng = np.random.default_rng(seed)
    cap = max_blocks * bs

    def normal(shape, dtype=bf16):
        return jnp.asarray(rng.standard_normal(shape, np.float32), dtype)

    def oracle(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    # -- decode: one new token per lane ---------------------------------
    # Positions cover an inactive lane (0), the first and last row of an
    # append tile, a block boundary and its neighbours, and the last
    # position the table can hold; the rest are random.
    special = [0, 1, 7, 8, 15, 16, 17, 31, 32, cap - bs - 1, cap - bs,
               cap - 2]
    positions = np.asarray(
        (special + list(rng.integers(1, cap // 8, size=lanes)))[:lanes],
        np.int32)
    active = positions > 0
    table = jnp.asarray(_tables(rng, np, positions, active.astype(int), bs,
                                max_blocks, num_blocks))
    pos = jnp.asarray(positions)
    q, kn, vn = (normal((lanes, 1, H, D)), normal((lanes, 1, KVH, D)),
                 normal((lanes, 1, KVH, D)))
    k_pages, v_pages = (normal((num_blocks, bs, F)),
                        normal((num_blocks, bs, F)))
    cos, sin = rope_angles(pos[:, None], D, cfg.rope_theta)
    live_blocks = np.ones((num_blocks,), bool)
    live_blocks[0] = False      # the null block takes inactive lanes' writes

    def gather_ref(q, kn, vn, kp, vp):
        q_r, k_r = apply_rope(q, cos, sin), apply_rope(kn, cos, sin)
        act = (pos > 0)[:, None]
        pk = llama._scatter_pages(kp, k_r, table, pos[:, None], act)
        pv = llama._scatter_pages(vp, vn, table, pos[:, None], act)
        return (ops.paged_decode_attention(q_r, pk, pv, table, pos + 1),
                pk, pv, q_r)

    want, wk, wv, q_r = oracle(gather_ref, q, kn, vn, k_pages, v_pages)
    got, gk, gv = kernel(pa.paged_decode_attention_fused)(
        q, kn, vn, cos, sin, k_pages, v_pages, table, pos)
    _close(np, "fused decode bf16: attention", got, want, rows=active)
    _close(np, "fused decode bf16: K pages", gk, wk, rows=live_blocks)
    _close(np, "fused decode bf16: V pages", gv, wv, rows=live_blocks)
    # The append is a read-modify-write of a whole tile: rows it did not
    # own must come back bit-identical (V is written unroped, so its pages
    # must match the scatter oracle exactly).
    assert bool(jnp.all(gv[1:] == wv[1:])), "fused append disturbed V rows"

    # The split kernel (the per-shard kernel of the tensor-parallel path).
    got = kernel(pa.paged_decode_attention_pallas)(q_r, wk, wv, table,
                                                   pos + 1)
    _close(np, "split decode bf16: attention", got, want, rows=active)

    # -- decode, int8 KV -------------------------------------------------
    kq_pages = jnp.asarray(rng.integers(-127, 128, (num_blocks, bs, F)),
                           jnp.int8)
    vq_pages = jnp.asarray(rng.integers(-127, 128, (num_blocks, bs, F)),
                           jnp.int8)
    ks = jnp.asarray(rng.uniform(0.004, 0.02, (num_blocks, bs, KVH)),
                     jnp.float32)
    vs = jnp.asarray(rng.uniform(0.004, 0.02, (num_blocks, bs, KVH)),
                     jnp.float32)

    def gather_ref_q(q, kn, vn, kp, vp, ks, vs):
        q_r, k_r = apply_rope(q, cos, sin), apply_rope(kn, cos, sin)
        act = (pos > 0)[:, None]
        pk, psk = llama._scatter_pages_quant(kp, ks, k_r, table,
                                             pos[:, None], act)
        pv, psv = llama._scatter_pages_quant(vp, vs, vn, table,
                                             pos[:, None], act)
        return (ops.paged_decode_attention_quant(
            q_r, pk, pv, psk, psv, table, pos + 1), pk, pv, psk, psv)

    want, wk, wv, wks, wvs = oracle(gather_ref_q, q, kn, vn, kq_pages,
                                    vq_pages, ks, vs)
    got, gk, gv, gks, gvs = kernel(pa.paged_decode_attention_fused_quant)(
        q, kn, vn, cos, sin, kq_pages, vq_pages, ks, vs, table, pos)
    _close(np, "fused decode int8-KV: attention", got, want, rows=active)
    _close(np, "fused decode int8-KV: V scales", gvs, wvs, rows=live_blocks)
    _close(np, "fused decode int8-KV: K scales", gks, wks, rows=live_blocks)
    # Codes are integers: the appended K row may differ by one step where
    # in-kernel rope rounds the other way; every other byte is identical.
    dk = np.abs(np.asarray(gk[1:], np.int32) - np.asarray(wk[1:], np.int32))
    assert dk.max() <= 1 and (dk > 0).sum() <= lanes * F, "int8 K codes off"
    assert bool(jnp.all(gv[1:] == wv[1:])), "int8 V codes off"

    # -- prefill family: fresh, continuation chunk, spec verify ----------
    def flash_case(name, S, start, lengths, quant):
        P = len(start)
        start_np = np.asarray(start, np.int32)
        len_np = np.asarray(lengths, np.int32)
        tbl = jnp.asarray(_tables(rng, np, start_np, len_np, bs, max_blocks,
                                  num_blocks))
        st, ln = jnp.asarray(start_np), jnp.asarray(len_np)
        qq, kk, vv = (normal((P, S, H, D)), normal((P, S, KVH, D)),
                      normal((P, S, KVH, D)))
        positions = st[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        valid = jnp.arange(S)[None, :] < ln[:, None]
        rows = np.asarray(valid)
        if quant:
            pk, psk = jax.jit(llama._scatter_pages_quant)(
                kq_pages, ks, kk, tbl, positions, valid)
            pv, psv = jax.jit(llama._scatter_pages_quant)(
                vq_pages, vs, vv, tbl, positions, valid)
            got = kernel(pa.flash_prefill_attention)(
                qq, pk, pv, tbl, st, ln, k_scale=psk, v_scale=psv)
            want = oracle(
                lambda *a: ops.paged_verify_attention(
                    a[0], llama.dequantize_kv(a[1], a[3]),
                    llama.dequantize_kv(a[2], a[4]), tbl, st, ln),
                qq, pk, pv, psk, psv)
        else:
            pk = jax.jit(llama._scatter_pages)(k_pages, kk, tbl, positions,
                                               valid)
            pv = jax.jit(llama._scatter_pages)(v_pages, vv, tbl, positions,
                                               valid)
            got = kernel(pa.flash_prefill_attention)(qq, pk, pv, tbl, st, ln)
            if not start_np.any():      # fresh prefill: dense causal oracle
                want = oracle(
                    lambda q, k, v: ops.causal_attention(
                        q, k, v, q_positions=positions, kv_len=ln),
                    qq, kk, vv)
            else:
                want = oracle(
                    lambda q, k, v: ops.paged_verify_attention(
                        q, k, v, tbl, st, ln), qq, pk, pv)
        _close(np, name, got, want, rows=rows)

    flash_case("flash prefill bf16: fresh S=256", 256, [0, 0, 0, 0],
               [256, 200, 1, 0], False)
    flash_case("flash prefill bf16: chunk S=128 over a cached prefix", 128,
               [256, 37, 512, 0], [128, 128, 50, 0], False)
    flash_case("flash prefill bf16: spec verify S=5", 5,
               list(positions[:8]), [5, 5, 3, 5, 1, 5, 5, 2], False)
    flash_case("flash prefill int8-KV: chunk S=128", 128,
               [256, 37, 512, 0], [128, 128, 50, 0], True)
    flash_case("flash prefill int8-KV: spec verify S=5", 5,
               list(positions[:8]), [5, 5, 3, 5, 1, 5, 5, 2], True)


def fused_run_checks(cfg, seed: int, lanes: int, max_blocks: int,
                     num_blocks: int, mean_tokens: int,
                     reps: int = 20) -> None:
    """The fused decode kernel at ``cfg``'s heads on ``lanes`` lanes of about
    ``mean_tokens`` cached tokens, on tables the allocator lays out in runs
    of 8 blocks (a group is ONE copy an array) and on the same pool with
    every block id moved at random (a copy a page): both outputs bit-equal,
    and each form's time a lane (in place, ``reps`` calls)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from k8s_llm_monitor_tpu.ops import pallas_attention as pa
    from k8s_llm_monitor_tpu.ops.rope import rope_angles
    from k8s_llm_monitor_tpu.serving.kv_cache import BlockAllocator

    H, KVH, D, bs = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_, 16
    interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(seed)
    cap = max_blocks * bs - 1
    pos = np.clip(rng.integers(1, 2 * mean_tokens, size=lanes), 1, cap)
    alloc = BlockAllocator(num_blocks, bs, table_blocks=max_blocks)
    table = np.zeros((lanes, max_blocks), np.int32)
    for b, p in enumerate(pos):
        row = alloc.alloc(int(p) + 1, at=0)
        table[b, :len(row)] = row
    move = np.concatenate([[0], 1 + rng.permutation(num_blocks - 1)])
    tables = {"runs": table, "pages": move[table].astype(np.int32)}
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    bf16 = jnp.bfloat16

    def normal(key, *shape):
        return jax.random.normal(key, shape, jnp.float32).astype(bf16)

    q, kn, vn = (normal(keys[0], lanes, 1, H, D),
                 normal(keys[1], lanes, 1, KVH, D),
                 normal(keys[2], lanes, 1, KVH, D))
    cos, sin = rope_angles(jnp.asarray(pos)[:, None], D, 10_000.0)
    inverse = jnp.asarray(np.argsort(move).astype(np.int32))
    kernel = jax.jit(functools.partial(pa.paged_decode_attention_fused,
                                       interpret=interpret),
                     donate_argnums=(5, 6))
    out, times = {}, {}
    for form, tbl in tables.items():
        kp = normal(keys[3], num_blocks, bs, KVH * D)
        vp = normal(keys[4], num_blocks, bs, KVH * D)
        if form == "pages":
            kp, vp = jnp.take(kp, inverse, axis=0), jnp.take(vp, inverse, axis=0)
        groups, runs = pa.fetch_groups(tbl, pos, bs)
        args = (jnp.asarray(tbl), jnp.asarray(pos, jnp.int32))
        got, kp, vp = kernel(q, kn, vn, cos, sin, kp, vp, *args)
        out[form] = np.asarray(got)
        jax.block_until_ready((kp, vp))
        t0 = time.perf_counter()
        for _ in range(reps):
            _, kp, vp = kernel(q, kn, vn, cos, sin, kp, vp, *args)
        jax.block_until_ready((kp, vp))
        times[form] = (time.perf_counter() - t0) / reps
        say(f"  fused decode, {form}: {H} q / {KVH} kv x {D}, {lanes} lanes "
            f"of {pos.mean():.0f} cached tokens, {runs} of {groups} groups "
            f"one copy: {times[form] * 1e3:.4f} ms a call, "
            f"{times[form] / lanes * 1e6:.3f} us a lane")
        del kp, vp
    same = np.array_equal(out["runs"], out["pages"])
    say(f"  fused decode: runs and pages {'bit-equal' if same else 'DIFFER'}; "
        f"pages / runs = {times['pages'] / times['runs']:.3f}")
    assert same, "fused decode: a run's copy changed the output"


def state_kernel_checks(cfg, seed: int, lanes: int,
                        tokens: int = 3072) -> None:
    """The recurrent-state kernels at ``cfg``'s widths (a description with
    Mamba-2 layers): ``ssm_decode_update`` on a pool of ``lanes`` lanes in
    place against its XLA form, a whole lane a grid step and half a lane,
    with idle rows (decay 1, input 0: the state must come back bit-equal)
    and a lane order that is not the identity; and ``ssm_chunk_scan_kernel``
    against ``ssm_chunk_scan`` on a packed stream of ``tokens`` tokens
    (segments starting mid-chunk, padding between and behind them, two
    wanted states in one chunk), with both forms' device times."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from k8s_llm_monitor_tpu.ops import ssm

    H, P, N, G = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                  cfg.ssm_state_size, cfg.mamba_n_groups)
    pack = ssm.state_pack(H, G, P)
    interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32))

    pool = normal(lanes, H // pack, N, pack * P)
    order = jnp.asarray(rng.permutation(lanes).astype(np.int32))
    idle = jnp.asarray(rng.random(lanes) < 0.25)
    decay = jnp.where(idle[:, None], 1.0, jnp.exp(-jnp.abs(normal(lanes, H))))
    dtx = jnp.where(idle[:, None, None], 0.0, normal(lanes, H, P))
    Bm, Cm = normal(lanes, G, N), normal(lanes, G, N)
    want_y, want_pool = jax.jit(ssm.ssm_decode_update_xla)(
        pool, order, decay, dtx, Bm, Cm)
    for rows in (0, H // pack // 2):
        got_y, got_pool = jax.jit(functools.partial(
            ssm.ssm_decode_update, block_rows=rows, interpret=interpret),
            donate_argnums=(0,))(pool + 0.0, order, decay, dtx, Bm, Cm)
        name = f"ssm_decode_update, {rows or H // pack} rows a grid step"
        _close(np, f"{name}: y", got_y, want_y, rows=~np.asarray(idle))
        _close(np, f"{name}: the pool", got_pool, want_pool)
        kept = np.asarray(order)[np.asarray(idle)]
        assert np.array_equal(np.asarray(got_pool)[kept],
                              np.asarray(pool)[kept]), (
            f"{name}: an idle row's state changed")

    # The admission scan: four segments of a packed stream, the first two
    # ending in one chunk, padding (dt 0) between the third and the fourth
    # and behind the last.
    dtype = jnp.float32 if interpret else jnp.bfloat16
    a, b, c = tokens // 3 + 5, tokens // 3 + 70, 2 * tokens // 3 + 33
    segments = [(0, a), (a, b), (b, c),
                (c + tokens // 12, tokens - tokens // 60)]
    first = np.zeros((1, tokens), bool)
    real = np.zeros(tokens, bool)
    for start, end in segments:
        first[0, start] = True
        real[start:end] = True
    dt = jnp.where(jnp.asarray(real)[None, :, None],
                   jax.nn.softplus(normal(1, tokens, H) - 1.0), 0.0)
    scan = (normal(1, tokens, H, P).astype(dtype), dt,
            -jnp.exp(jnp.asarray(rng.uniform(0, 2.5, H), jnp.float32)),
            normal(1, tokens, G, N).astype(dtype),
            normal(1, tokens, G, N).astype(dtype), jnp.asarray(first),
            jnp.asarray([end - 1 for _, end in segments], jnp.int32))
    Q = cfg.ssm_chunk_size

    def xla(*scan):
        y, states = ssm.ssm_chunk_scan(*scan, chunk=Q)
        return y, ssm.pack_state(states, pack)

    forms = {"xla": jax.jit(xla),
             "kernel": jax.jit(functools.partial(
                 ssm.ssm_chunk_scan_kernel, chunk=Q, interpret=interpret))}
    out = {name: f(*scan) for name, f in forms.items()}
    rows = np.asarray(real)
    # Against the largest value, not elementwise: the two forms' float32
    # sums differ in order, and a bfloat16 operand that rounds the other way
    # moves a sum of terms of ~100 by a few of its ulps where it cancels.
    for part, got, want in (("y", out["kernel"][0][0][rows],
                             out["xla"][0][0][rows]),
                            ("states", out["kernel"][1], out["xla"][1])):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        err = float(np.abs(got - want).max() / np.abs(want).max())
        ok = np.isfinite(got).all() and err <= 2.0 ** -6
        say(f"  kernel ssm_chunk_scan_kernel at T={tokens}: {part}: "
            f"max|err| / max|want| = {err:.3e} {'ok' if ok else 'MISMATCH'}")
        assert ok, f"ssm_chunk_scan_kernel at T={tokens}: {part} ({err})"
    for name, f in forms.items():
        jax.block_until_ready(f(*scan))
        t0 = time.perf_counter()
        for _ in range(5):
            jax.block_until_ready(f(*scan))
        say(f"  ssm_chunk_scan {name} form at T={tokens}: "
            f"{(time.perf_counter() - t0) / 5 * 1e3:.3f} ms")


def mamba1_kernel_checks(cfg, seed: int, lanes: int,
                         tokens: int = 4096) -> None:
    """The Mamba-1 kernels at ``cfg``'s widths (a description with Mamba-1
    layers): ``mamba1_decode_update`` on a pool of ``lanes`` lanes in place
    against its XLA form, with idle rows (step 0: the state must come back
    bit-equal) and a lane order that is not the identity; and
    ``mamba1_scan_kernel`` against ``mamba1_scan`` on a packed stream of
    ``tokens`` tokens (segments starting mid-chunk, padding between and
    behind them, two wanted states in one chunk), with both forms' times."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from k8s_llm_monitor_tpu.ops import ssm

    C, N = cfg.mamba_inner, cfg.ssm_state_size
    interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32))

    A = -jnp.exp(jnp.asarray(np.log(np.arange(1, N + 1))[:, None]
                             + 0.1 * rng.standard_normal((N, C)), jnp.float32))
    pool = normal(lanes, N, C)
    order = jnp.asarray(rng.permutation(lanes).astype(np.int32))
    idle = jnp.asarray(rng.random(lanes) < 0.25)
    dt = jnp.where(idle[:, None], 0.0, jax.nn.softplus(normal(lanes, C) - 4.0))
    dtu = dt * normal(lanes, C)
    Bm, Cm = normal(lanes, N), normal(lanes, N)
    want_y, want_pool = jax.jit(ssm.mamba1_decode_update_xla)(
        pool, order, dt, dtu, A, Bm, Cm)
    update = jax.jit(functools.partial(ssm.mamba1_decode_update,
                                       interpret=interpret),
                     donate_argnums=(0,))
    got_y, got_pool = update(pool + 0.0, order, dt, dtu, A, Bm, Cm)
    _close(np, "mamba1_decode_update: y", got_y, want_y,
           rows=~np.asarray(idle))
    _close(np, "mamba1_decode_update: the pool", got_pool, want_pool)
    kept = np.asarray(order)[np.asarray(idle)]
    assert np.array_equal(np.asarray(got_pool)[kept],
                          np.asarray(pool)[kept]), (
        "mamba1_decode_update: an idle row's state changed")
    fresh = normal(lanes, N, C)
    fresh = jax.block_until_ready(update(fresh, order, dt, dtu, A, Bm, Cm)[1])
    t0 = time.perf_counter()
    for _ in range(5):
        fresh = update(fresh, order, dt, dtu, A, Bm, Cm)[1]
    jax.block_until_ready(fresh)
    say(f"  mamba1_decode_update kernel at {lanes} lanes: "
        f"{(time.perf_counter() - t0) / 5 * 1e3:.3f} ms")

    # The admission scan: four segments of a packed stream, the first two
    # ending in one chunk, padding (dt 0) between the third and the fourth
    # and behind the last.
    dtype = jnp.float32 if interpret else jnp.bfloat16
    a, b, c = tokens // 3 + 5, tokens // 3 + 70, 2 * tokens // 3 + 33
    segments = [(0, a), (a, b), (b, c),
                (c + tokens // 12, tokens - tokens // 60)]
    first = np.zeros((1, tokens), bool)
    real = np.zeros(tokens, bool)
    for start, end in segments:
        first[0, start] = True
        real[start:end] = True
    dts = jnp.where(jnp.asarray(real)[None, :, None],
                    jax.nn.softplus(normal(1, tokens, C) - 4.0), 0.0)
    scan = (normal(1, tokens, C).astype(dtype), dts, A, normal(1, tokens, N),
            normal(1, tokens, N), jnp.asarray(first),
            jnp.asarray([end - 1 for _, end in segments], jnp.int32))
    forms = {"xla": jax.jit(ssm.mamba1_scan),
             "kernel": jax.jit(functools.partial(ssm.mamba1_scan_kernel,
                                                 interpret=interpret))}
    out = {name: f(*scan) for name, f in forms.items()}
    rows = np.asarray(real)
    for part, got, want in (("y", out["kernel"][0][0][rows],
                             out["xla"][0][0][rows]),
                            ("states", out["kernel"][1], out["xla"][1])):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        err = float(np.abs(got - want).max() / np.abs(want).max())
        ok = np.isfinite(got).all() and err <= 2.0 ** -6
        say(f"  kernel mamba1_scan_kernel at T={tokens}: {part}: "
            f"max|err| / max|want| = {err:.3e} {'ok' if ok else 'MISMATCH'}")
        assert ok, f"mamba1_scan_kernel at T={tokens}: {part} ({err})"
    for name, f in forms.items():
        jax.block_until_ready(f(*scan))
        t0 = time.perf_counter()
        for _ in range(5):
            jax.block_until_ready(f(*scan))
        say(f"  mamba1_scan {name} form at T={tokens}: "
            f"{(time.perf_counter() - t0) / 5 * 1e3:.3f} ms")


def sparse_kernel_checks(cfg, seed: int, lanes: int,
                         context: int = 1536, rows: int = 1024) -> None:
    """The selected-attention and window kernels at ``cfg``'s widths (a
    latent description with an indexer and window layers) against their XLA
    forms: the index-score decode kernel over index-key pages, the latent
    decode kernel under a keep mask (several bursts, one of them with nothing
    kept, an idle lane) and over one burst of a lane's ring of the window
    store, and the packed prefill kernels under a band and under the
    selection (two prompts of ``rows`` and ``rows // 3`` tokens in blocks of
    512 against the dense masked form)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from k8s_llm_monitor_tpu.ops import attention as ops
    from k8s_llm_monitor_tpu.ops import pallas_attention as pa
    from k8s_llm_monitor_tpu.ops import sparse

    layers = range(cfg.num_layers)
    full = next(cfg.latent_geometry(i) for i in layers
                if cfg.latent_geometry(i).indexed)
    band = next(cfg.latent_geometry(i) for i in layers
                if cfg.latent_geometry(i).window)
    interpret = jax.default_backend() != "tpu"
    dtype = jnp.float32 if interpret else jnp.bfloat16
    rng = np.random.default_rng(seed)
    bs = 16

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape, np.float32) * scale, dtype)

    def pool(width, lens, NB):
        pages = normal(1 + lanes * NB, bs, width)
        tables = np.zeros((lanes, NB), np.int32)
        for b in range(lanes):
            n = -(-int(lens[b]) // bs)
            tables[b, :n] = 1 + b * NB + np.arange(n)
        return pages, jnp.asarray(tables)

    NB = -(-context // bs)
    lens = rng.integers(context // 3, context + 1, size=lanes).astype(np.int32)
    lens[1] = 0
    live = lens > 0
    # -- decode: index scores, then attention under the selection -----------
    idx_pages, tables = pool(full.index_dim, lens, NB)
    qI = normal(lanes, 1, full.index_heads, full.index_dim)
    wI = jnp.asarray(rng.standard_normal((lanes, 1, full.index_heads)),
                     jnp.float32)
    want = ops.index_scores_decode(qI, wI, idx_pages, tables, jnp.asarray(lens))
    got = jax.jit(functools.partial(pa.index_scores_decode_pallas,
                                    interpret=interpret))(
        qI, wI, idx_pages, tables, jnp.asarray(lens))
    seen = np.arange(NB * bs)[None, :] < lens[:, None]
    _close(np, f"index scores, decode: {lanes} lanes x {full.index_heads} heads",
           np.where(seen, np.asarray(got, np.float32)[:, :NB * bs], 0.0),
           np.where(seen, np.asarray(want, np.float32), 0.0))
    keep = np.array(sparse.topk_keep(want, jnp.asarray(seen),
                                     min(full.index_topk, context // 3)))
    burst = min(512, context // 2)
    keep[0, :burst] = False                    # a whole burst of nothing
    keep[0, burst] = True
    pages, tables = pool(full.page_width, lens, NB)
    q = normal(lanes, 1, full.num_heads, full.page_width, scale=0.05)
    kw = dict(v_width=full.kv_lora_rank, keep=jnp.asarray(keep),
              name="sparse_latent_decode_attention")
    want = jax.jit(functools.partial(ops.latent_decode_attention, **kw))(
        q, pages, tables, jnp.asarray(lens))
    got = jax.jit(functools.partial(pa.latent_decode_attention_pallas,
                                    interpret=interpret, **kw))(
        q, pages, tables, jnp.asarray(lens))
    _close(np, f"selected decode attention: {full.num_heads} heads x "
           f"{full.page_width} lanes, mask form", got, want, rows=live)
    # -- decode over the window store: one burst of a lane's ring -----------
    ring = -(-band.window // bs)
    wlens = np.minimum(lens, band.window).astype(np.int32)
    pages, tables = pool(band.page_width, wlens, ring)
    q = normal(lanes, 1, band.num_heads, band.page_width, scale=0.05)
    kw = dict(v_width=band.kv_lora_rank, burst=ring,
              name="window_latent_decode_attention")
    want = jax.jit(functools.partial(ops.latent_decode_attention, **kw))(
        q, pages, tables, jnp.asarray(wlens))
    got = jax.jit(functools.partial(pa.latent_decode_attention_pallas,
                                    interpret=interpret, **kw))(
        q, pages, tables, jnp.asarray(wlens))
    _close(np, f"window decode attention: {band.num_heads} heads x "
           f"{band.page_width} lanes, a ring of {ring} blocks", got, want,
           rows=live)
    # -- packed prefill under a band and under the selection -----------------
    n0, n1 = rows, rows // 3
    T = -(-(n0 + n1) // 512) * 512
    offset = jnp.asarray([0, n0, n0 + n1], jnp.int32)
    length = jnp.asarray([n0, n1, 0], jnp.int32)
    for g, name in ((band, "window"), (full, "selected")):
        heads = min(g.num_heads, 8)          # the dense oracle's [H, S, S]
        q = normal(T, heads, g.qk_head_dim, scale=0.3)
        k = normal(T, heads, g.qk_head_dim, scale=0.3)
        v = normal(T, heads, g.v_head_dim)
        index, topk = None, 0
        if g.indexed:
            topk = min(g.index_topk, rows // 4)
            index = (normal(T, g.index_heads, g.index_dim),
                     normal(T, g.index_dim),
                     jnp.asarray(rng.standard_normal((T, g.index_heads)),
                                 jnp.float32))
        got = jax.jit(functools.partial(
            pa.latent_prefill_attention_packed, scale=g.qk_head_dim ** -0.5,
            row_len=rows, window=g.window, topk=topk, interpret=interpret))(
            q, k, v, offset, length, index=index)
        for at, n in ((0, n0), (n0, n1)):
            pos = jnp.arange(n, dtype=jnp.int32)[None]
            allowed = sparse.allowed_keys(pos, jnp.asarray([n]), n, g.window)
            if index is not None:
                cut = lambda x: x[None, at:at + n]              # noqa: E731
                allowed = sparse.topk_keep(
                    sparse.index_scores(cut(index[0]), cut(index[2]),
                                        cut(index[1])), allowed, topk)
            want = sparse.masked_attention(
                q[None, at:at + n], k[None, at:at + n], v[None, at:at + n],
                allowed, scale=g.qk_head_dim ** -0.5)
            _close(np, f"{name} packed prefill: a prompt of {n} tokens, "
                   f"{heads} heads", got[at:at + n], want[0])


def grouped_kernel_checks(cfg, seed: int, lanes: int,
                          rows_per_expert=(1, 3, 8, 16, 32, 64, 128, 256, 512,
                                           768, 1024),
                          tile_rows=(None,)) -> list[dict]:
    """The expert layer's grouped product at ``cfg``'s widths (a routed
    description), in the three forms of ``ops/grouped.py``: the stream
    kernel's int32 bit-equal to the compiler's ``ragged_dot`` on every row a
    group covers, the tiles kernel's dequantised result bit-equal to
    ``ragged_dot`` followed by the dequantisation ``_expert_rows`` writes —
    at a decode step's shape (``lanes`` x experts per token sorted rows, of
    which a chip's held share are live), at ``rows_per_expert`` rows an
    expert, and at an admission window of 16,384 rows whose live rows lie on
    half of the experts (what a window of the share layer sees) — and each
    form's device time *with its dequantisation* (the sweep ``product_form``'s
    bounds cite; on the CPU the kernels run interpreted and the times say
    nothing).  The stream form is timed up to 128 rows an expert; the tiles
    form at each of ``tile_rows`` (None: ``grouped.TILES_ROWS``).  Returns
    the lines' numbers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from k8s_llm_monitor_tpu.ops import grouped

    G = cfg.experts_held_
    narrow, wide = cfg.moe_latent_size or cfg.hidden_size, cfg.expert_width
    on_tpu = jax.default_backend() == "tpu"
    rng = np.random.default_rng(seed)
    interpret = not on_tpu
    reps = 16 if on_tpu else 1
    BF16, F32 = jnp.bfloat16, jnp.float32

    def dequantised(product):
        """``_expert_rows``'s W8A8 branch around an int32 product."""
        def fn(rows, kernels, sizes, row_scale, scale, rows_e):
            y32 = product(rows, kernels, sizes)
            return (y32.astype(F32) * row_scale * scale[rows_e]).astype(BF16)
        return fn

    def tiles(tm):
        def fn(rows, kernels, sizes, row_scale, scale, rows_e):
            return grouped.grouped_tiles_product(
                rows, kernels, sizes, row_scale, scale, dtype=BF16,
                tile_rows=tm, interpret=interpret)
        return fn

    stream = dequantised(functools.partial(grouped.grouped_rows_product,
                                           interpret=interpret))
    compiler = dequantised(grouped.grouped_rows_product_xla)

    def seconds(fn, rows, *rest) -> float:
        """Device time of one product: ``reps`` of them in one program, on
        rows that differ, each whole result the loop's carry (so no part of
        a product can be left out), the fastest of three runs."""
        def many(rows, *rest):
            def one(y, i):
                return fn(rows ^ i.astype(jnp.int8), *rest), None
            y0 = jnp.zeros((rows.shape[0], rest[0].shape[2]), BF16)
            return jax.lax.scan(one, y0, jnp.arange(reps))[0]
        run = jax.jit(many)
        run(rows, *rest).block_until_ready()
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            run(rows, *rest).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best / reps

    lines = []

    def case(name, M, sizes, K, N, kernels, scale):
        live, hit = int(sizes.sum()), int((sizes > 0).sum())
        rows = jnp.asarray(rng.integers(-127, 128, (M, K), dtype=np.int8))
        row_scale = jnp.asarray(rng.random((M, 1), dtype=np.float32) / 127)
        rows_e = jnp.asarray(np.minimum(
            np.repeat(np.arange(G + 1), [*sizes, M - live]), G - 1), jnp.int32)
        args = (rows, kernels, jnp.asarray(sizes), row_scale, scale, rows_e)
        want32 = jax.jit(grouped.grouped_rows_product_xla)(*args[:3])
        got32 = jax.jit(functools.partial(grouped.grouped_rows_product,
                                          interpret=interpret))(*args[:3])
        assert np.array_equal(np.asarray(got32)[:live],
                              np.asarray(want32)[:live]), (
            f"{name}: the stream kernel differs from ragged_dot")
        del want32, got32
        want = np.asarray(jax.jit(compiler)(*args))[:live]
        line = {"case": name, "m": M, "g": G, "k": K, "n": N, "live": live,
                "hit": hit, "form": grouped.product_form(M, G, K, N, jnp.int8),
                "tiles_us": {}}
        for tm in tile_rows:
            got = np.asarray(jax.jit(tiles(tm))(*args))[:live]
            assert np.array_equal(got.view(np.uint16), want.view(np.uint16)), (
                f"{name}: the tiles kernel (row tile {tm}) differs from "
                f"ragged_dot and its dequantisation")
            tm = tm or grouped.TILES_ROWS
            line["tiles_us"][tm] = seconds(tiles(tm), *args) * 1e6
        if live <= 128 * G:
            line["stream_us"] = seconds(stream, *args) * 1e6
        line["compiler_us"] = seconds(compiler, *args) * 1e6
        best = min(line["tiles_us"].values())
        lines.append(line)
        say(f"grouped product {name}: [{M}, {K}] x [{G}, {K}, {N}], "
            f"{live} live rows on {hit} experts, bit-equal; us with the "
            f"dequantisation: stream "
            f"{line.get('stream_us', float('nan')):.1f}, tiles "
            + ", ".join(f"{t:.1f} (tile {tm})"
                        for tm, t in line["tiles_us"].items())
            + f" = {2 * live * K * N / 393e12 / best * 1e8:.1f}% of the int8 "
            f"peak, {hit * K * N / 819e9 / best * 1e8:.1f}% of its kernels' "
            f"bytes at 819 GB/s, compiler {line['compiler_us']:.1f}; "
            f"form={line['form']}")

    even = lambda live: rng.multinomial(  # noqa: E731
        live, np.ones(G) / G).astype(np.int32)
    for K, N in ((narrow, wide), (wide, narrow)):
        kernels = jnp.asarray(rng.integers(-127, 128, (G, K, N),
                                           dtype=np.int8))
        scale = jnp.asarray(rng.random((G, N), dtype=np.float32) / 127)
        M = lanes * cfg.num_experts_per_tok
        case("decode", M, even(M * G // cfg.num_experts), K, N, kernels, scale)
        for r in rows_per_expert:
            case(f"{r} rows an expert", r * G, even(r * G), K, N, kernels,
                 scale)
        if max(rows_per_expert) >= 128:
            half = np.zeros(G, np.int32)
            half[G // 4:G // 4 + G // 2] = rng.multinomial(
                16_384, np.ones(G // 2) * 2 / G)
            case("a window on half the experts", 16_384, half, K, N, kernels,
                 scale)
    return lines


# ---------------------------------------------------------------------------
# phase 2: the real server, over HTTP
# ---------------------------------------------------------------------------


class CompileCounter:
    """Backend compilations JAX reports (jax.monitoring), for information;
    the zero-recompile gate is the engine's jit cache sizes, as in
    devtools/traceguard.py."""

    def __init__(self) -> None:
        import jax.monitoring

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if "backend_compile" in event:
            self.count += 1
            self.seconds += duration


def _post(port: int, path: str, body: dict, timeout: float = 900.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def _get(port: int, path: str, text: bool = False):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=60.0) as resp:
        return resp.read().decode() if text else json.loads(resp.read())


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def serve_round(srv, port: int, tag: str, backend_name: str,
                long_chars: int) -> dict:
    """One round: 8 concurrent ``POST /api/v1/query`` (six plain, one SSE
    stream, one prompt longer than the largest prefill bucket) plus one
    grammar-constrained root-cause verdict (``POST /api/v1/analyze``).

    Requests are queued in a fixed order while the engine's step thread is
    parked, then released together: the admission batches — and so the
    shapes each program sees — are the same in every round, which is what
    lets the last round assert it compiled nothing.  Each round uses its own
    tenant, so its prefix-cache pattern starts cold like the first round's.
    """
    sup = srv.engine_supervisor()
    tenant = f"smoke-{tag}"
    results: dict[str, dict] = {}
    errors: list[str] = []

    def post(name: str, path: str, body: dict) -> None:
        t0 = time.monotonic()
        with _post(port, path, {**body, "tenant": tenant}) as resp:
            reply = json.loads(resp.read())
        results[name] = {"s": time.monotonic() - t0, "body": reply}

    def stream(name: str, question: str) -> None:
        t0 = time.monotonic()
        deltas, done = 0, None
        with _post(port, "/api/v1/query", {"question": question,
                                           "tenant": tenant,
                                           "stream": True}) as resp:
            assert resp.headers.get("Content-Type") == "text/event-stream"
            for raw in resp:
                line = raw.decode().strip()
                if not line.startswith("data: "):
                    continue
                ev = json.loads(line[6:])
                assert "error" not in ev, ev
                if ev.get("done"):
                    done = ev
                    break
                deltas += 1
        results[name] = {"s": time.monotonic() - t0, "deltas": deltas,
                         "done": done}

    def guarded(fn, *args):
        def run():
            try:
                fn(*args)
            except Exception as exc:  # recorded, then fails the round below
                errors.append(f"{args[0]}: {exc!r}")
        return threading.Thread(target=run, daemon=True)

    jobs = [guarded(post, f"plain{i}", "/api/v1/query",
                    {"question": f"Why is pod web-{i} in CrashLoopBackOff?"})
            for i in range(6)]
    jobs.append(guarded(stream, "stream", "Why is pod api-0 not Ready yet?"))
    jobs.append(guarded(post, "long", "/api/v1/query",
                        {"question": "Explain this log: " + "x" * long_chars}))
    jobs.append(guarded(post, "verdict", "/api/v1/analyze",
                        {"type": "root_cause",
                         "parameters": {"namespace": "default",
                                        "symptom": "pods restarting"}}))

    # Park the step thread so the whole round is queued before any of it
    # is admitted (submissions wait in the service's queue meanwhile).
    gate = threading.Event()
    parked = threading.Thread(
        target=lambda: sup.call(lambda _e: gate.wait(30.0), timeout=60.0),
        daemon=True)
    parked.start()
    time.sleep(0.2)
    for j in jobs:
        j.start()
        time.sleep(0.15)
    gate.set()
    for j in jobs:
        j.join(timeout=900.0)
        assert not j.is_alive(), "a request hung"
    parked.join(timeout=5.0)
    assert not errors, errors

    for name, r in results.items():
        if "body" in r:
            body = r["body"]
            assert body["status"] == "success", (name, body)
            assert body["result"]["model"] == backend_name, (name, body)
    assert results["stream"]["done"]["model"] == backend_name
    v = results["verdict"]["body"]["result"]["verdict"]
    assert set(v) == {"severity", "component", "root_cause",
                      "recommendation", "confidence"}, v
    return results


def one_chip(seed: int, model: str = MODEL, max_tokens: int = 9,
             kernels: bool = True) -> None:
    import jax
    import numpy as np

    from k8s_llm_monitor_tpu.devtools.traceguard import program_cache_size
    from k8s_llm_monitor_tpu.models.config import PRESETS
    from k8s_llm_monitor_tpu.monitor.cluster import (
        FakeCluster,
        seed_demo_cluster,
    )
    from k8s_llm_monitor_tpu.monitor.config import load_config
    from k8s_llm_monitor_tpu.monitor.server import build_server
    from k8s_llm_monitor_tpu.utils.quantize import param_bytes

    compiles = CompileCounter()
    config = load_config(None)
    config.llm.provider = "tpu"
    config.llm.tpu.model = model
    # Greedy, short answers: reproducible from the seed, so two rounds of
    # the same requests run the same programs.  Nine tokens are one from
    # prefill and one 8-step fused decode call: each further decode length
    # is another whole-model program to compile (about a minute cold).
    config.llm.temperature = 0.0
    config.llm.max_tokens = max_tokens
    config.server.host = "127.0.0.1"
    config.server.port = _free_port()
    tcfg = config.llm.tpu

    if kernels:
        t0 = time.monotonic()
        say(f"phase kernels: {model} widths, pool {tcfg.kv_blocks} x 16, "
            f"{tcfg.max_batch} lanes, atol=rtol={ATOL}")
        kernel_checks(PRESETS[model], seed, tcfg.kv_blocks, 16, 64,
                      tcfg.max_batch)
        # The fused decode kernel's run copies against a copy a page, at
        # the Qwen2 cell's call (64 lanes, 96-block tables, 6,144 blocks).
        fused_run_checks(PRESETS[model], seed, 64, 96, 6144, 530)
        # The served nemotron_h description's own kernel, and the attention
        # kernels at its head shape (16 query heads a kv head).
        hybrid = PRESETS["nemotron3-super-120b-a12b-22l"]
        state_kernel_checks(hybrid, seed, 2 * tcfg.max_batch)
        kernel_checks(hybrid, seed, tcfg.kv_blocks, 16, 64, tcfg.max_batch)
        # The two routed descriptions' expert products at a decode step's
        # shape (64 lanes) and at one row tile an expert.
        for routed in (hybrid, PRESETS["kanana-2-30b-a3b-12l"]):
            grouped_kernel_checks(routed, seed, 64, rows_per_expert=(32,))
        # The selected-attention and window kernels of the description with
        # two latent geometries.
        sparse_kernel_checks(PRESETS["dots3-note-prev-5l"], seed, 16)
        # The jamba description's Mamba-1 kernels at its 256 lanes, and the
        # attention kernels at its head shape (20 query heads on one kv
        # head).
        jamba = PRESETS["jamba2-3b"]
        mamba1_kernel_checks(jamba, seed, 256)
        fused_run_checks(jamba, seed, 256, 256, 65_537, 750)
        kernel_checks(jamba, seed, tcfg.kv_blocks, 16, 64, tcfg.max_batch)
        say(f"phase kernels: ok in {time.monotonic() - t0:.1f} s "
            "(information)")

    # -- boot the real server the way cmd/server.py does ----------------
    t0, boot_c0 = time.monotonic(), compiles.seconds
    srv = build_server(config, backend=seed_demo_cluster(FakeCluster()))
    backend = srv.analysis.backend
    sup = srv.engine_supervisor()
    assert sup is not None and backend.name != "template", backend.name
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    port = config.server.port
    for _ in range(200):
        try:
            _get(port, "/health")
            break
        except OSError:
            time.sleep(0.05)
    engine = sup.engine
    cfg = engine.cfg
    setup_s = time.monotonic() - t0
    say(f"phase boot: {backend.name}, {cfg.name} layers={cfg.num_layers} "
        f"hidden={cfg.hidden_size} heads={cfg.num_heads}/{cfg.num_kv_heads}"
        f"x{cfg.head_dim_} vocab={cfg.vocab_size} quantize={tcfg.quantize} "
        f"weights={param_bytes(engine.params) / 2**30:.2f} GiB")
    say(f"phase boot: decode_path={engine.decode_path} "
        f"prefill_path={engine.prefill_path} kv_quant="
        f"{engine.kv_quant or 'none'} spec_k={engine.ecfg.spec_k} "
        f"buckets={engine.ecfg.prefill_buckets} "
        f"capacity={engine.capacity_tokens} tokens/sequence")
    say(f"phase boot: set-up {setup_s:.1f} s incl. weights and the "
        f"first-compile gate, {compiles.seconds - boot_c0:.1f} s of it "
        f"backend compile ({compiles.count} compilations so far; "
        "information)")
    assert PRESETS[model].num_layers == cfg.num_layers, "depth was cut"

    # No kernel may run interpreted, and `auto` must have resolved to the
    # kernels: on a TPU that is the fused decode kernel and flash prefill.
    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        assert engine.decode_path == "fused", engine.decode_path
        assert engine.prefill_path == "flash", engine.prefill_path
        for impl in (engine._attn_impl, engine._prefill_attn):
            assert not getattr(impl, "keywords", {}).get("interpret"), impl

    # -- two warm-up rounds, then the round that must compile nothing ---
    # (The first round meets a cold prefix cache and the speculative
    # decoder's first, optimistic dispatch; the second runs the steady
    # programs.  Each tenant is new to the cache, so the third round sees
    # the second's shapes.)
    top = engine.ecfg.prefill_buckets[-1]
    observed: dict[str, int] = {}
    svc = sup.service
    inner = svc.observer

    def observer(request_id, toks, result):
        observed[request_id] = observed.get(request_id, 0) + len(toks)
        if result is not None:
            assert result.finish_reason != "error", result.error
        inner(request_id, toks, result)

    svc.observer = observer
    for tag in ("warm-a", "warm-b", "steady"):
        before_prog, before_ev = program_cache_size(engine), compiles.count
        before_reqs = len(observed)
        t0 = time.monotonic()
        results = serve_round(srv, port, tag, backend.name,
                              long_chars=top + 200)
        wall = time.monotonic() - t0
        new_prog = program_cache_size(engine) - before_prog
        walls = sorted(r["s"] for r in results.values())
        say(f"phase serve[{tag}]: 9 HTTP requests ok in {wall:.1f} s; "
            f"request wall s min/median/max = {walls[0]:.2f}/"
            f"{walls[len(walls) // 2]:.2f}/{walls[-1]:.2f}; SSE deltas="
            f"{results['stream']['deltas']}; new engine programs="
            f"{new_prog}; compile events="
            f"{compiles.count - before_ev} (information)")
        # 8 queries + the verdict's free-text and constrained generations.
        assert len(observed) - before_reqs == 10, (
            "engine requests", len(observed) - before_reqs)
    assert new_prog == 0, (
        f"the steady round compiled {new_prog} new engine program(s)")
    assert all(n >= 1 for n in observed.values()), (
        "a request generated no token", observed)

    # -- what the server says about itself -------------------------------
    stats = _get(port, "/api/v1/stats")["engine"]
    health = _get(port, "/health")
    assert health["ready"] and health["status"] == "healthy", health
    assert health["lifecycle"]["restarts"] == 0, health["lifecycle"]
    assert health["engine"]["dispatch_failures"] == 0, health["engine"]
    assert health["engine"]["requeues"] == 0, health["engine"]
    assert health["engine"]["sheds"] == 0, health["engine"]
    assert sum(stats["shed_by_class"].values()) == 0, stats
    assert stats["queue_depth"] == 0 and stats["busy_slots"] == 0, stats
    assert sup.engine is engine, "the engine was rebuilt"
    chunk_progs = (engine._prefill_chunk_greedy._cache_size()
                   + engine._prefill_chunk_sample._cache_size()
                   + engine._prefill_chunk_sample_fsm._cache_size())
    say(f"phase serve: prefix cache hits={stats['prefix_cache']['hits']} "
        f"misses={stats['prefix_cache']['misses']}; prefill rounds by "
        f"bucket={dict(sorted(engine.prefill_bucket_rounds.items()))}; "
        f"chunk-prefill programs compiled={chunk_progs}; engine requests="
        f"{len(observed)} tokens={sum(observed.values())}; constrained="
        f"{engine.constrained_requests}; spec verify steps="
        f"{engine.spec_verify_steps}")
    assert chunk_progs >= 1, "no chunked (paged-prefix) prefill ran"
    assert engine.constrained_requests >= 3, engine.constrained_requests
    if not results["stream"]["deltas"]:
        say("note: the SSE stream carried no text delta before its done "
            "event: random weights emit token ids above the byte "
            "tokenizer's range, which decode to no text (tokens are counted "
            "at the engine, above)")
    if engine.capacity_tokens <= top:
        say(f"note: at the server's defaults a sequence holds "
            f"{engine.capacity_tokens} tokens, less than the largest "
            f"bucket ({top}): the long prompt was cut to capacity at "
            "submit and the chunk program ran through prefix-cache hits")

    # -- the compiled programs carry the kernels -------------------------
    if on_tpu:
        decode_key = next(k for k in engine._decode_cache
                          if k[0] != "spec" and k[1:] == (False,) * 3)
        for name, prog in (
                ("prefill", engine._prefill_greedy),
                ("prefill-chunk", engine._prefill_chunk_greedy),
                ("decode", engine._decode_cache[decode_key])):
            n = _kernel_calls(jax, engine, name, prog)
            say(f"phase serve: compiled {name} program holds {n} "
                "tpu_custom_call kernels")
            assert n >= cfg.num_layers, (name, n)

    # /metrics tells the same story, device memory gauges included (the
    # exporter reads device.memory_stats()).
    metrics = _get(port, "/metrics", text=True)
    assert (f'engine_decode_path_info{{path="{engine.decode_path}"}} 1'
            in metrics), "decode path gauge"
    assert (f'engine_prefill_path_info{{path="{engine.prefill_path}"}} 1'
            in metrics), "prefill path gauge"
    if on_tpu:
        assert "device_memory_used_bytes{" in metrics, "no HBM gauge"
        assert "device_memory_limit_bytes{" in metrics, "no HBM limit gauge"

    peak = jax.devices()[0].memory_stats() or {}
    say(f"phase serve: compilations={compiles.count} "
        f"({compiles.seconds:.0f} s of backend compile) peak_bytes_in_use="
        f"{peak.get('peak_bytes_in_use', 'not reported')} bytes_limit="
        f"{peak.get('bytes_limit', 'not reported')} (information)")

    srv.request_shutdown()
    sup.shutdown(grace_s=5.0)


def _kernel_calls(jax, engine, name: str, prog) -> int:
    """Count ``tpu_custom_call`` in the compiled text of an engine program
    at a shape the serving rounds ran (an AOT compile of the same jitted
    function: the persistent cache serves it when it is on)."""
    import jax.numpy as jnp

    ec = engine.ecfg
    i32 = jnp.int32
    shape = lambda *s: jax.ShapeDtypeStruct(s, i32)  # noqa: E731
    pages = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), engine.pages)
    NB = ec.max_blocks_per_seq
    if name == "prefill":       # one chip: a packed stream of 32 tokens
        R = ec.max_prefills_per_step
        args = (engine.params, shape(32), (shape(R), shape(R)), pages,
                shape(R, NB))
    elif name == "prefill-chunk":   # the rounds' 8-lane prefix-hit shape
        args = (engine.params, shape(8, 256), shape(8), shape(8), pages,
                shape(8, 32))
    else:
        B = ec.max_slots
        args = (engine.params, shape(B), shape(B), shape(B), pages,
                shape(B, NB), shape())
    return prog.lower(*args).compile().as_text().count("tpu_custom_call")


# ---------------------------------------------------------------------------
# --chips 4: the tensor-parallel engine against the one-chip engine
# ---------------------------------------------------------------------------


def tp_logits(engine, prompt, forced=None):
    """Prefill one prompt and take three decode steps with the engine's own
    params, pages, attention impls, shardings and (when staged) overlap
    step — the building blocks its serving programs scan over.  Decode feeds
    ``forced`` tokens when given (so two engines score the same sequence),
    else the engine's own greedy tokens.  Returns (four next-token logit
    rows, the three tokens fed, the compiled text)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from k8s_llm_monitor_tpu.models import llama

    cfg, ec = engine.cfg, engine.ecfg
    n = len(prompt)
    bucket = engine._bucket(n)
    blocks = np.arange(1, (n + 8) // ec.block_size + 2, dtype=np.int32)
    table = np.zeros((1, ec.max_blocks_per_seq), np.int32)
    table[0, :len(blocks)] = blocks
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :n] = prompt
    step = engine._overlap_step
    feed = np.full((3,), -1, np.int32) if forced is None else forced

    @jax.jit
    def run(params, pages, tokens, table, feed):
        lengths = jnp.asarray([n], jnp.int32)
        lg, pages = llama.prefill(params, cfg, tokens, lengths, pages,
                                  table, attn_impl=engine._prefill_attn)
        out, fed, ctx = [lg[0]], [], lengths
        for i in range(3):
            tok = jnp.where(feed[i] >= 0, feed[i],
                            jnp.argmax(lg, axis=-1)).astype(jnp.int32)
            fed.append(tok[0])
            if step is not None:
                lg, pages = step(params, tok, ctx, pages, table)
            else:
                lg, pages = llama.decode_step(
                    params, cfg, tok, ctx, pages, table,
                    attn_impl=engine._attn_impl)
            out.append(lg[0])
            ctx = ctx + 1
        return jnp.stack(out), jnp.stack(fed)

    args = (engine.params, engine.pages, tokens, table, feed)
    compiled = run.lower(*args).compile()
    logits, fed = compiled(*args)
    return (np.asarray(logits, np.float32), np.asarray(fed, np.int32),
            compiled.as_text())


def rel_l2(got, want):
    """Per-row relative L2 error of logit rows (1.41 = unrelated rows)."""
    import numpy as np

    centered = want - want.mean(axis=-1, keepdims=True)
    return (np.linalg.norm(got - want, axis=-1)
            / np.linalg.norm(centered, axis=-1))


def four_chips(seed: int, model: str = MODEL, mesh_shape: str = "1,1,4"
               ) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from k8s_llm_monitor_tpu.models import llama
    from k8s_llm_monitor_tpu.monitor.analysis import LocalEngineBackend
    from k8s_llm_monitor_tpu.monitor.config import TPULLMConfig
    from k8s_llm_monitor_tpu.serving.engine import (
        EngineConfig,
        InferenceEngine,
    )
    from k8s_llm_monitor_tpu.utils.quantize import init_params_quantized

    devs = jax.devices()
    on_tpu = devs[0].platform == "tpu"
    rng = np.random.default_rng(seed)

    def per_device_bytes(tree) -> list[int]:
        used = {d.id: 0 for d in devs}
        for leaf in jax.tree.leaves(tree):
            for sh in leaf.addressable_shards:
                used[sh.device.id] += sh.data.nbytes
        return [used[d.id] for d in devs]

    # -- the sharded path: the same preset through the same backend the
    # server builds, only ``mesh_shape`` set.  Its first-compile gate has
    # then served one request through the normal submit path on the mesh.
    t0 = time.monotonic()
    backend = LocalEngineBackend.from_config(
        TPULLMConfig(model=model, mesh_shape=mesh_shape))
    staged = backend.engine
    say(f"phase tp: built {backend.name} mesh={mesh_shape} in "
        f"{time.monotonic() - t0:.1f} s (information); decode_path="
        f"{staged.decode_path} prefill_path={staged.prefill_path} "
        f"tp_overlap={staged.tp_overlap}")
    assert staged.mesh is not None and staged.tp_overlap, "auto chose GSPMD"
    assert backend.supervisor.restarts == 0
    if on_tpu:      # a CPU rehearsal interprets the kernels
        assert (staged.decode_path, staged.prefill_path) == (
            "pallas", "flash"), (staged.decode_path, staged.prefill_path)

    wbytes = per_device_bytes(staged.params)
    kbytes = per_device_bytes(staged.pages)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]
    say(f"phase tp: weight GiB per device = "
        f"{[round(b / 2**30, 2) for b in wbytes]}; KV pool MiB per device = "
        f"{[round(b / 2**20, 1) for b in kbytes]}; device bytes_in_use = "
        f"{in_use} (information)")
    # Spread, not parked on device 0: every device holds its share, and
    # the pool is split, not replicated.
    assert max(wbytes) < 1.25 * min(wbytes), wbytes
    assert max(kbytes) == min(kbytes) and min(kbytes) > 0, kbytes
    assert sum(kbytes) == sum(
        x.nbytes for x in jax.tree.leaves(staged.pages)), "pool replicated"
    if on_tpu and None not in in_use:
        assert max(in_use) < 1.5 * min(in_use), in_use

    # Same mesh, same sharded weights, GSPMD's own collective schedule.
    gspmd = InferenceEngine(
        staged.cfg, staged.params,
        dataclasses.replace(staged.ecfg, tp_overlap="off"),
        tokenizer=backend.tokenizer, mesh=staged.mesh)
    assert not gspmd.tp_overlap

    # -- what it is compared with: the one-chip engine, same seeded
    # weights (the backend's key), same engine configuration — and that
    # engine's own XLA oracle paths (gather decode, dense prefill), which
    # measure how far two CORRECT programs drift apart on these weights.
    prompt = [1] + [int(t) for t in rng.integers(3, 259, size=90)]
    t0 = time.monotonic()
    params1 = init_params_quantized(jax.random.PRNGKey(0), staged.cfg)
    sized = dict(max_slots=staged.ecfg.max_slots,
                 num_blocks=staged.ecfg.num_blocks,
                 spec_k=staged.ecfg.spec_k)
    one = InferenceEngine(staged.cfg, params1, EngineConfig(**sized),
                          tokenizer=backend.tokenizer)
    want, fed, _ = tp_logits(one, prompt)
    oracle = InferenceEngine(
        staged.cfg, params1,
        EngineConfig(decode_path="gather", prefill_path="dense", **sized),
        tokenizer=backend.tokenizer)
    drift = rel_l2(tp_logits(oracle, prompt, fed)[0], want)
    say(f"phase tp: one-chip engine (decode_path={one.decode_path} "
        f"prefill_path={one.prefill_path}) and its oracle paths "
        f"({oracle.decode_path}/{oracle.prefill_path}) built and run in "
        f"{time.monotonic() - t0:.1f} s (information); logit rows' relative "
        f"L2 drift between the two one-chip programs = "
        f"{[round(float(r), 4) for r in drift]}")
    assert np.isfinite(want).all() and (drift < 0.7).all(), drift

    # Logit-level agreement, every engine scoring the same tokens.  With
    # w8a8 each layer rounds its activations to int8, and a rounding that
    # flips turns a last-bit difference (another reduction order, another
    # attention kernel) into a 1/127 step; over 28 layers of random weights
    # two correct programs drift apart by tens of percent of a logit row's
    # spread.  So the bound is measured, not assumed: the mesh programs may
    # sit no further from the one-chip engine than twice what its own
    # oracle paths do (floor 15%), and always far below 1.41, which is what
    # unrelated weights or a wrong shard give.
    bound = max(0.15, 2.0 * float(drift.max()))
    for name, engine in (("on", staged), ("off", gspmd)):
        logits, _, text = tp_logits(engine, prompt, fed)
        ops_seen = re.findall(r"= \S+ ([a-z\-]+)\(", text)
        colls = {c: sum(o.startswith(c) for o in ops_seen) for c in
                 ("all-reduce", "reduce-scatter", "all-gather")}
        kernels = text.count("tpu_custom_call")
        rel = rel_l2(logits, want)
        say(f"phase tp[overlap {name}]: program holds {kernels} "
            f"tpu_custom_call kernels; collective ops {colls}; logit rows' "
            f"relative L2 error vs one chip = "
            f"{[round(float(r), 4) for r in rel]} (bound {bound:.3f})")
        if on_tpu:
            assert kernels >= engine.cfg.num_layers, kernels
        # Both schedules carry collectives.  GSPMD's program all-reduces
        # after each row-parallel projection; the staged program
        # (parallel/overlap.py) asks for psum_scatter + all_gather halves.
        # The TPU compiler turns a small reduce-scatter into an all-reduce,
        # so the staged program is told apart by what it must have either
        # way: an all-gather per half, two halves per layer.
        assert colls["all-reduce"] + colls["reduce-scatter"] > 0, colls
        if name == "on":
            assert colls["all-gather"] >= 2 * engine.cfg.num_layers, colls
        assert np.isfinite(logits).all()
        assert (rel < min(bound, 0.7)).all(), (rel, bound)
    backend.supervisor.shutdown(grace_s=1.0)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the tensor-parallel phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" or len(devs) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX reports "
              f"{len(devs)} x {dev.platform}:{dev.device_kind}",
              file=sys.stderr)
        return 2

    import jaxlib

    from k8s_llm_monitor_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # packaging metadata only; never gates the run
        libtpu = "unknown"
    cache_dir, warm = configure_compile_cache()
    say(f"chip_smoke: jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {libtpu}; {len(devs)} x {dev.platform}:{dev.device_kind}; "
        f"seed {args.seed}")
    say(f"chip_smoke: compile cache {cache_dir} "
        f"({'warm' if warm else 'cold'})")

    t0 = time.monotonic()
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    say(f"chip_smoke: all phases ok in {time.monotonic() - t0:.1f} s "
        "(information)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
