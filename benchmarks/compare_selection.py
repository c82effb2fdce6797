"""Compare a configuration with learned sparse attention with its reference,
separating *which keys* from *what attention does with them*.

    python3 benchmarks/compare_selection.py --config dots3-note-prev-w8a8 --seed <n>

``compare_reference.py`` (which this file may not edit and whose pieces it
uses) runs one reference layer at a time on the engine's own input to it.  A
layer whose keys an indexer selects adds a discrete choice to that: the
engine scores in bfloat16 on int8-rounded projections, the reference in
float32, and a key near the ``index_topk``-th score falls on one side or the
other.  With seeded random weights attention is close to a mean over the
kept keys, so each key that differs moves a full layer's attention output by
about ``sqrt(2 / index_topk)`` of its norm: a dozen such keys are a tenth.
So here the reference's full layers are run **on the selection the timed
kernels made** (``InferenceEngine.score_logits(..., selection=True)``: the
scores and the keep mask of ``sparse_latent_prefill_index_scores`` /
``sparse_latent_prefill_select`` at the prompt's positions and of
``sparse_latent_decode_index_scores`` + the counting passes at each decode
step, as attention was handed them; ``references/<model_type>.py``:
``attention(selected=...)``), which holds the engine to the reference in
everything but the choice, and the choice is reported beside it:

* ``update`` — as ``compare_reference``: relative L2 of each layer's update,
  median over the prompt's positions and over the decode positions, against
  the limits file's ``update_rel_l2_median``;
* ``index_scores_rel_l2`` — the kernels' indexer scores ``I(t, .)`` against
  the reference's, relative L2 a query over the keys before it (median,
  largest), against ``index_scores_rel_l2_median``;
* ``selection_overlap`` — the share of the reference's ``S_t`` that the
  kernels' selection holds, over the queries past ``index_topk`` (median,
  least), against ``selection_overlap_least``;
* ``xla_form_differs`` — information, no limit: in how many keys a query the
  kernels' selection differs from the program's XLA forms
  (``ops/sparse.py``: ``index_scores``, ``topk_keep``) recomputed from the
  layer's input outside the timed programs.

Controls on the first ``--controls`` prompts, reference against reference,
same inputs, each of which has to come out as not correct: activations
rounded to 4 bits where the configuration says 8 — over the update limit in
every layer, and, in the full layers, its indexer (queries, keys and head
weights off 4-bit activations) over the score limit and under the overlap
limit; and the **selection control** — the selection left out (every earlier
key) — over the update limit on every full layer over the decode positions
past ``index_topk``.  The last line is one JSON object; exit 0 when every
limit was met and every control missed, 1 when not, 2 without the chip.
"""

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmarks.compare_reference import (  # noqa: E402
    BENCH_DIR,
    layer_by_layer,
    load_reference,
    log,
    rel_l2,
)


def xla_form_selection(cfg, layer, li: int, states: np.ndarray,
                       block: int = 256) -> np.ndarray:
    """keep [S, S] bool of indexed layer ``li`` by the program's XLA forms,
    recomputed from the layer's input ``states`` [S, hidden] in the served
    arithmetic, outside the timed programs."""
    import jax
    import jax.numpy as jnp

    from k8s_llm_monitor_tpu.models import llama
    from k8s_llm_monitor_tpu.ops import sparse
    from k8s_llm_monitor_tpu.ops.norms import rms_norm

    g = cfg.latent_geometry(li)
    S = states.shape[0]
    pad = -S % block

    @jax.jit
    def run(layer, x):
        x = x.astype(jnp.dtype(cfg.dtype))[None]
        pos = jnp.arange(x.shape[1], dtype=jnp.int32)[None]
        cos, sin = llama._rope_of(cfg, llama._rope_tables(cfg, pos), li)
        h = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps,
                     cfg.rmsnorm_unit_offset)
        *_, cq = llama._latent_qkv(layer, cfg, g, h, cos, sin)
        qI, kI, w = llama._index_qk(layer, cfg, g, h, cq, cos, sin)
        fit = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))  # noqa: E731
        qb = fit(qI).reshape(-1, block, *qI.shape[2:])
        wb = fit(w).reshape(-1, block, w.shape[-1])
        t0 = jnp.arange(qb.shape[0], dtype=jnp.int32) * block

        def one(args):
            q, wt, start = args
            scores = sparse.index_scores(q[None], wt[None], kI)       # [1, b, S]
            allowed = sparse.allowed_keys(
                (start + jnp.arange(block, dtype=jnp.int32))[None],
                jnp.asarray([S], jnp.int32), S)
            return sparse.topk_keep(scores, allowed, g.index_topk)[0]

        return jax.lax.map(one, (qb, wb, t0)).reshape(-1, S)[:S]

    return np.asarray(run(layer, jnp.asarray(states)), bool)


def against(scores, keep, want: dict, topk: int) -> dict:
    """An indexer's (scores, keep) [S, S] against the reference's ``want``
    (``scores``, ``keep``): the scores' relative L2 a query over the keys
    before it, and the share of the reference's selection held, over the
    queries past ``topk``."""
    S = scores.shape[0]
    tri = np.tril(np.ones((S, S), bool))
    serr = rel_l2(np.where(tri, scores, 0.0), np.where(tri, want["scores"], 0.0))
    past = np.arange(S) >= topk
    both = ((keep & want["keep"]).sum(1)
            / np.maximum(want["keep"].sum(1), 1))[past]
    return {"index_scores_rel_l2_median": float(np.median(serr)),
            "index_scores_rel_l2_max": float(serr.max()),
            "selecting_queries": int(past.sum()),
            "selection_overlap_median": float(np.median(both)) if past.any() else 1.0,
            "selection_overlap_least": float(both.min()) if past.any() else 1.0}


def compare(config_name: str, seed: int, *, traffic: str, prompts: int,
            decode: int, controls: int, preset: str = None,
            engine: dict = None, limits: dict = None) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import draw, system
    from benchmarks.harness.registry import Registry

    registry = Registry()
    entries = {c["name"]: c for c in registry.benchmark["configs"]}
    config = json.loads((registry.root / entries[config_name]["file"]).read_text())
    mix = json.loads((BENCH_DIR / "traffic" / f"{traffic}.json").read_text())
    if limits is None:
        limits = json.loads((BENCH_DIR / "references" /
                             f"limits.{config_name}.json").read_text())[config_name]
    ref = load_reference(config["model_type"])

    engine_obj, svc = system.build(config, seed, preset_override=preset,
                                   engine_overrides=engine, log=log)
    try:
        cfg = engine_obj.cfg
        dist = dict(mix["prompt_tokens"])
        if preset is not None:   # a CPU rehearsal: lengths that fit its pool
            cap = engine_obj.capacity_tokens - decode - 1
            dist.update(min=min(dist["min"], cap // 4), median=cap // 2, max=cap)
        lengths = draw.lognormal_int(prompts, draw.rng_for(seed, 0), dist, prompts)
        ids = draw.token_ids(lengths, draw.rng_for(seed, 2), cfg.vocab_size)
        scored = []
        for n, prompt in enumerate(ids):
            t = time.monotonic()
            scored.append(svc.call(
                lambda e, p=prompt: e.score_logits(p, decode, hidden=True,
                                                   selection=True),
                timeout=3000.0))
            log(f"prompt {n}: {len(prompt)} tokens + {decode} steps scored in "
                f"{time.monotonic() - t:.1f}s")
        xla_form = [{li: xla_form_selection(cfg, engine_obj.params["layers"][li],
                                            li, states[li]) for li in chosen}
                    for _, states, chosen in scored]
        params = jax.device_get(engine_obj.params)
        act_quant = bool(cfg.act_quant)
        topk = cfg.index_topk
        if preset is not None:   # the rehearsal's model, not the file's
            config = ref.config_of(cfg)
    finally:
        svc.stop(timeout=30.0)

    out = {"config": config_name, "seed": seed, "limits": limits, "prompts": []}
    ok = True

    def held(name: str, value: float, what: str, above: bool = False) -> bool:
        good = value >= limits[name] if above else value <= limits[name]
        print(f"{what}: {value:.5f}  limit {limits[name]:.5f}  "
              f"{'ok' if good else 'OVER' if not above else 'UNDER'}")
        return good

    def missed(name: str, value: float, what: str, above: bool = False) -> bool:
        bad = value < limits[name] if above else value > limits[name]
        print(f"{what}: {value:.5f}  limit {limits[name]:.5f}  "
              f"{'not correct, as it must be' if bad else 'PASSES'}")
        return bad

    with jax.default_device(jax.devices("cpu")[0]), \
            jax.default_matmul_precision("highest"):
        for n, (prompt, (rows, states, chosen)) in enumerate(zip(ids, scored)):
            L, S = len(prompt), states.shape[1]
            t = time.monotonic()
            entry = {"tokens": L, "layers": []}
            update = lambda li, quant=act_quant, **kw: np.asarray(  # noqa: E731
                ref.layer_forward(params["layers"][li], config,
                                  jnp.asarray(states[li]), quant, **kw)[0]
            ) - states[li]
            wants, probes = {}, {}
            for li in range(len(params["layers"])):
                kw = {}
                if li in chosen:
                    probes[li] = {}
                    kw = dict(selected=chosen[li][1], probe=probes[li])
                wants[li] = update(li, **kw)
                err = rel_l2(states[li + 1] - states[li], wants[li])
                row = {"layer": li,
                       "prompt_median": float(np.median(err[:L])),
                       "decode_median": float(np.median(err[L:])),
                       "decode_max": float(err[L:].max())}
                tag = f"prompt {n} ({L} tokens) layer {li}"
                ok &= held("update_rel_l2_median", row["prompt_median"],
                           f"{tag} update on the kernels' selection, median "
                           f"over the prompt")
                ok &= held("update_rel_l2_median", row["decode_median"],
                           f"{tag} update on the kernels' selection, median "
                           f"over the decode steps (max {row['decode_max']:.4f})")
                if li in chosen:
                    row.update(against(*chosen[li], probes[li], topk))
                    ok &= held("index_scores_rel_l2_median",
                               row["index_scores_rel_l2_median"],
                               f"{tag} the kernels' index scores, median over "
                               f"queries (max {row['index_scores_rel_l2_max']:.4f})")
                    ok &= held("selection_overlap_least",
                               row["selection_overlap_least"],
                               f"{tag} share of the reference's selection the "
                               f"kernels' holds, least over "
                               f"{row['selecting_queries']} queries (median "
                               f"{row['selection_overlap_median']:.4f})",
                               above=True)
                    differs = (chosen[li][1] ^ xla_form[n][li]).sum(1)
                    row["xla_form_differs"] = {
                        "prompt_median": float(np.median(differs[:L])),
                        "prompt_max": int(differs[:L].max()),
                        "decode_median": float(np.median(differs[L:])),
                        "decode_max": int(differs[L:].max())}
                    print(f"{tag} keys a query in which the kernels' selection "
                          f"differs from the XLA forms recomputed from the "
                          f"layer's input (no limit): {row['xla_form_differs']}")
                entry["layers"].append(row)
            entry["reference_s"] = time.monotonic() - t
            if n < controls:
                # The reference on its own selection is what a control is
                # held against; the layers without an indexer have it above.
                own = {li: update(li) if li in chosen else wants[li]
                       for li in wants}
                low, caught = [], True
                for li in own:
                    probe = {} if li in chosen else None
                    err = rel_l2(update(li, 4, **({"probe": probe}
                                                  if li in chosen else {})),
                                 own[li])
                    row = {"layer": li, "prompt_median": float(np.median(err[:L]))}
                    tag = f"prompt {n} control act_int4 layer {li}"
                    caught &= missed("update_rel_l2_median", row["prompt_median"],
                                     f"{tag} (reference one precision lower, "
                                     f"against the reference) update, median "
                                     f"over the prompt")
                    if li in chosen:
                        row.update(against(probe["scores"], probe["keep"],
                                           probes[li], topk))
                        caught &= missed("index_scores_rel_l2_median",
                                         row["index_scores_rel_l2_median"],
                                         f"{tag} its index scores, median "
                                         f"over queries")
                        caught &= missed("selection_overlap_least",
                                         row["selection_overlap_least"],
                                         f"{tag} share of the reference's "
                                         f"selection its own holds, least over "
                                         f"{row['selecting_queries']} queries",
                                         above=True)
                    low.append(row)
                entry["control_act_int4"] = low
                # The selection left out: only positions past index_topk can
                # tell, so the control looks at the decode positions there.
                first = max(L, min(topk, S - 1))
                everything = []
                for li in chosen:
                    err = rel_l2(update(li, select_all=True), own[li])[first:]
                    row = {"layer": li, "decode_median": float(np.median(err))}
                    caught &= S > topk and missed(
                        "update_rel_l2_median", row["decode_median"],
                        f"prompt {n} control select_all layer {li} (the "
                        f"selection left out, against the reference) update, "
                        f"median over the positions past {max(L, topk)}")
                    everything.append(row)
                entry["control_select_all"] = everything
                entry["controls_caught"] = bool(caught and everything)
                ok &= entry["controls_caught"]
            out["prompts"].append(entry)
    out["ok"] = bool(ok)
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--traffic", default="casefile-loops")
    parser.add_argument("--prompts", type=int, default=2)
    parser.add_argument("--decode", type=int, default=12)
    parser.add_argument("--controls", type=int, default=1)
    args = parser.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("compare_selection: no TPU here", file=sys.stderr)
        return 2
    out = compare(args.config, args.seed, traffic=args.traffic,
                  prompts=args.prompts, decode=args.decode,
                  controls=args.controls)
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    import os

    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)   # as run.py: no TPU runtime teardown after the step thread
