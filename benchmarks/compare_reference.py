"""Compare a configuration's served path with its plain reference.

    python3 benchmarks/compare_reference.py --config kanana2-30b-a3b-w8a8 --seed <n>

Builds the system exactly as a cell does (``harness/system.py:build``: the
configuration file, weights from ``--seed``), draws ``--prompts`` prompts of
the lengths of ``--traffic`` from the seed, and for each asks the engine for
what it computed (``InferenceEngine.score_logits(..., hidden=True)`` on the
step thread): the engine's own prefill into the engine's own pages, then
``--decode`` single steps through the paged cache, with the logit rows and
the residual stream of every position before each layer and after the last.

The reference (``references/<model_type>.py``, the benchmark's own copy:
float32, highest precision, no cache, a loop over the chosen experts) runs on
the host's CPU from the same weights, ONE LAYER AT A TIME ON THE ENGINE'S OWN
INPUT to that layer, over the whole sequence (prompt and decode positions
together, so a decode position's keys are what the engine cached).  Why not
whole-model logits: with seeded random weights, int8-rounded activations and
a discrete router, twelve layers amplify a bfloat16 rounding into an
uncorrelated logit row (relative L2 0.5-0.9 between two correct programs,
PERF.md section 6) — a limit there admits anything.  One layer does not
amplify: its error is the rounding of one layer's arithmetic.

Per layer it prints, beside their limits (``references/limits.json``):

* ``update`` — the relative L2 error of the layer's update (output minus
  input) per position, its median over the prompt's positions and over the
  decode positions (those went through the cache and the decode kernel);
* then, as information and with no limit, the logit rows against the
  reference's final norm and head on the engine's last residual stream
  (beside the same head on an int8-rounded input) and against the
  reference's own whole forward (the chaotic number).

The controls, on the first ``--controls`` prompts: the reference computed one
precision below what the configuration states — activations rounded to 4 bits
where it says 8, and the cache's contents rounded to int8 where it says
bfloat16 — against the reference itself, same inputs.  At least one has to
come out over the limit (the first does, by five to ten times; the second
reads what the engine reads, 2-4%, and cannot be seen by it: with random
weights the softmax averages hundreds of keys and the cache's rounding with
them).  The last line is one JSON object; exit 0 when every
limit was met and a control missed, 1 when not, 2 without the chip.
``cell.py``'s ``correct`` does not call this yet (a ``benchmark`` PR wires it
in).
"""

import argparse
import importlib.util
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

BENCH_DIR = pathlib.Path(__file__).resolve().parent


def log(msg: str) -> None:
    print(f"[compare {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


def load_reference(model_type: str):
    path = BENCH_DIR / "references" / f"{model_type}.py"
    spec = importlib.util.spec_from_file_location(f"_bench_ref_{model_type}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def rel_l2(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Relative L2 error of each row."""
    got, want = got.astype(np.float64), want.astype(np.float64)
    return (np.linalg.norm(got - want, axis=-1)
            / np.maximum(np.linalg.norm(want, axis=-1), 1e-30))


def layer_by_layer(ref, params, config, states, n_prompt, act_quant,
                   control: dict = None) -> list[dict]:
    """One reference layer at a time on the engine's input to it.  With a
    ``control`` the comparison is reference-at-lower-precision against
    reference (same inputs); without, engine against reference."""
    import jax
    import jax.numpy as jnp

    out = []
    with jax.default_matmul_precision("highest"):
        for li, layer in enumerate(params["layers"]):
            x = jnp.asarray(states[li])
            want = np.asarray(ref.layer_forward(layer, config, x, act_quant)[0]) - states[li]
            if control:
                quant = control.get("act_quant", act_quant)
                kw = {k: v for k, v in control.items() if k != "act_quant"}
                got = np.asarray(ref.layer_forward(layer, config, x, quant, **kw)[0]) - states[li]
            else:
                got = states[li + 1] - states[li]
            err = rel_l2(got, want)
            entry = {"layer": li,
                     "prompt_median": float(np.median(err[:n_prompt])),
                     "prompt_p95": float(np.quantile(err[:n_prompt], 0.95))}
            if len(err) > n_prompt:
                entry["decode_median"] = float(np.median(err[n_prompt:]))
                entry["decode_max"] = float(err[n_prompt:].max())
            out.append(entry)
    return out


def compare(config_name: str, seed: int, *, traffic: str, prompts: int,
            decode: int, controls: int, whole: bool = True, preset: str = None,
            engine: dict = None, limits: dict = None) -> dict:
    import jax

    from benchmarks.harness import draw, system
    from benchmarks.harness.registry import Registry

    registry = Registry()
    entries = {c["name"]: c for c in registry.benchmark["configs"]}
    config = json.loads((registry.root / entries[config_name]["file"]).read_text())
    mix = json.loads((BENCH_DIR / "traffic" / f"{traffic}.json").read_text())
    if limits is None:
        limits = json.loads(
            (BENCH_DIR / "references" / "limits.json").read_text())[config_name]
    ref = load_reference(config["model_type"])

    engine_obj, svc = system.build(config, seed, preset_override=preset,
                                   engine_overrides=engine, log=log)
    try:
        vocab = engine_obj.cfg.vocab_size
        dist = dict(mix["prompt_tokens"])
        if preset is not None:   # a CPU rehearsal: lengths that fit its pool
            cap = engine_obj.capacity_tokens - decode - 1
            dist.update(min=min(dist["min"], cap // 4), median=cap // 2, max=cap)
        lengths = draw.lognormal_int(prompts, draw.rng_for(seed, 0), dist, prompts)
        ids = draw.token_ids(lengths, draw.rng_for(seed, 2), vocab)
        scored = []
        for n, prompt in enumerate(ids):
            t = time.monotonic()
            scored.append(svc.call(
                lambda e, p=prompt: e.score_logits(p, decode, hidden=True),
                timeout=3000.0))
            log(f"prompt {n}: {len(prompt)} tokens + {decode} steps scored in "
                f"{time.monotonic() - t:.1f}s")
        params = jax.device_get(engine_obj.params)
        act_quant = bool(engine_obj.cfg.act_quant)
        if preset is not None:   # the rehearsal's model, not the file's
            config = ref.config_of(engine_obj.cfg)
    finally:
        svc.stop(timeout=30.0)

    out = {"config": config_name, "seed": seed, "limits": limits, "prompts": []}
    ok = True

    def held(name: str, value: float, what: str) -> bool:
        good = value <= limits[name]
        print(f"{what}: {value:.5f}  limit {limits[name]:.5f}  "
              f"{'ok' if good else 'OVER'}")
        return good

    with jax.default_device(jax.devices("cpu")[0]):
        for n, (prompt, (rows, states)) in enumerate(zip(ids, scored)):
            L = len(prompt)
            t = time.monotonic()
            layers = layer_by_layer(ref, params, config, states, L, act_quant)
            entry = {"tokens": L, "layers": layers}
            for row in layers:
                tag = f"prompt {n} ({L} tokens) layer {row['layer']}"
                ok &= held("update_rel_l2_median", row["prompt_median"],
                           f"{tag} update, median over the prompt (p95 "
                           f"{row['prompt_p95']:.4f})")
                if "decode_median" in row:
                    ok &= held("update_rel_l2_median", row["decode_median"],
                               f"{tag} update, median over the decode steps "
                               f"(max {row['decode_max']:.4f})")
            import jax.numpy as jnp
            with jax.default_matmul_precision("highest"):
                last = ref.rms_norm(jnp.asarray(states[-1][L - 1:]),
                                    params["final_norm"], config["rms_norm_eps"])
                w_head = ref.widen(params["lm_head"])
                head = np.asarray(last @ w_head)
                head_low = np.asarray(ref.round_int8(last) @ w_head)
            # The head (shared by every model; weight-only): information.  On
            # the chip its bfloat16 result and scales read what an
            # int8-rounded input reads (0.7% both, PR 26), so no limit can lie
            # between the two and none is set.
            entry["head_rel_l2"] = [float(e) for e in rel_l2(rows, head)]
            entry["head_int8_input_rel_l2"] = [float(e) for e in rel_l2(head_low, head)]
            print(f"prompt {n} logit rows against the reference's head on the "
                  f"engine's last residual stream (no limit): rel_l2 max "
                  f"{max(entry['head_rel_l2']):.5f}; the reference's head on an "
                  f"int8-rounded input against itself: "
                  f"{min(entry['head_int8_input_rel_l2']):.5f}-"
                  f"{max(entry['head_int8_input_rel_l2']):.5f}")
            if whole:
                fed = [int(np.argmax(r)) for r in rows[:-1]]
                want, _ = ref.forward(params, config, prompt + fed,
                                      act_quant=act_quant,
                                      logit_positions=list(range(L - 1, L + decode)))
                entry["whole_model_rel_l2"] = [float(e) for e in rel_l2(rows, want)]
                print(f"prompt {n} logit rows against the reference's whole "
                      f"forward (chaotic; no limit): rel_l2 "
                      f"{min(entry['whole_model_rel_l2']):.3f}-"
                      f"{max(entry['whole_model_rel_l2']):.3f}")
            entry["reference_s"] = time.monotonic() - t
            if n < controls:
                caught = False
                for name, control in (("act_int4", {"act_quant": 4}),
                                      ("cache_int8", {"cache_int8": True})):
                    if name == "act_int4" and not act_quant:
                        continue
                    low = layer_by_layer(ref, params, config, states, L,
                                         act_quant, control)
                    worst = max(r["prompt_median"] for r in low)
                    entry[f"control_{name}"] = low
                    over = worst > limits["update_rel_l2_median"]
                    caught |= over
                    print(f"prompt {n} control {name} (reference one precision "
                          f"lower, against the reference): update median, "
                          f"worst layer {worst:.5f}  limit "
                          f"{limits['update_rel_l2_median']:.5f}  "
                          f"{'not correct, as it must be' if over else 'passes: this limit cannot see it'}")
                ok &= caught
                entry["control_caught"] = caught
            out["prompts"].append(entry)
    out["ok"] = bool(ok)
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--traffic", default="evidence-loops")
    parser.add_argument("--prompts", type=int, default=2)
    parser.add_argument("--decode", type=int, default=12)
    parser.add_argument("--controls", type=int, default=1,
                        help="prompts that also get the lower-precision controls")
    parser.add_argument("--whole", type=int, choices=(0, 1), default=1,
                        help="also the (chaotic) whole-model logits, as information")
    args = parser.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("compare_reference: no TPU here", file=sys.stderr)
        return 2
    out = compare(args.config, args.seed, traffic=args.traffic,
                  prompts=args.prompts, decode=args.decode,
                  controls=args.controls, whole=bool(args.whole))
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    import os

    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)   # as run.py: no TPU runtime teardown after the step thread
