"""Compare a configuration's served path with its plain reference, layer by
layer, with its limit from its own file: ``compare_reference.py``'s
comparison for a configuration whose head is its embedding (tied) and whose
limits live beside ``references/limits.json``.

    python3 benchmarks/compare_layers.py --config jamba2-3b-w8a8 \\
        --traffic reasoning-loops --seed <n>

What is compared, and why one layer at a time on the engine's own input, is
``compare_reference.py``'s docstring; this file reuses its
``layer_by_layer``; the reference runs on JAX's default device (the chip:
float32 at ``jax.default_matmul_precision("highest")``, as the reference
sets it) rather than the host, whose float32 products at these widths take
minutes a layer.  ``--tokens`` gives the prompts' lengths; without it they
are drawn from ``--traffic``'s mix.  The limit is
``references/limits.<config>.json``.  Per
prompt it prints each layer's update error (median over the prompt's
positions, and over the decode positions, which went through the state pool
and the decode kernels) beside the limit; then the controls on the first
``--controls`` prompts (the reference one precision lower against itself:
activations rounded to 4 bits where the configuration says 8, the cache's
keys and values to int8 where it says bfloat16) — at least one has to come
out over the limit.  As information, the logit rows against the reference's
final norm and tied head on the engine's last residual stream.  The last
line is one JSON object; exit 0 when every limit was met and a control
missed, 1 when not, 2 without the chip.
"""

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmarks import compare_reference as base  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent


def compare(config_name: str, seed: int, *, traffic: str, prompts: int,
            decode: int, controls: int, tokens: list = None,
            preset: str = None, engine: dict = None,
            limits: dict = None, built: tuple = None) -> dict:
    """``built``: an (engine, service) of this configuration already up and
    idle (a harness session's), used and left running; else one is built
    from ``seed`` and stopped."""
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
    limit = limits["update_rel_l2_median"]
    ref = base.load_reference(config["model_type"])

    engine_obj, svc = built or system.build(
        config, seed, preset_override=preset, engine_overrides=engine,
        log=base.log)
    try:
        vocab = engine_obj.cfg.vocab_size
        dist = dict(mix["prompt_tokens"])
        if preset is not None:   # a CPU rehearsal: lengths that fit its pool
            cap = engine_obj.capacity_tokens - decode - 1
            dist.update(min=min(dist["min"], cap // 4), median=cap // 2, max=cap)
        lengths = (np.asarray(tokens) if tokens else draw.lognormal_int(
            prompts, draw.rng_for(seed, 0), dist, prompts))
        ids = draw.token_ids(lengths, draw.rng_for(seed, 2), vocab)
        scored = []
        for n, prompt in enumerate(ids):
            t = time.monotonic()
            scored.append(svc.call(
                lambda e, p=prompt: e.score_logits(p, decode, hidden=True),
                timeout=3000.0))
            base.log(f"prompt {n}: {len(prompt)} tokens + {decode} steps "
                     f"scored in {time.monotonic() - t:.1f}s")
        params = jax.device_get(engine_obj.params)
        act_quant = bool(engine_obj.cfg.act_quant)
        if preset is not None:   # the rehearsal's model, not the file's
            config = ref.config_of(engine_obj.cfg)
    finally:
        if built is None:
            svc.stop(timeout=30.0)

    out = {"config": config_name, "seed": seed, "limits": limits, "prompts": []}
    ok = True
    for n, (prompt, (rows, states)) in enumerate(zip(ids, scored)):
        L = len(prompt)
        t = time.monotonic()
        layers = base.layer_by_layer(ref, params, config, states, L,
                                     act_quant)
        entry = {"tokens": L, "layers": layers}
        for row in layers:
            for part in ("prompt", "decode"):
                if f"{part}_median" not in row:
                    continue
                value = row[f"{part}_median"]
                good = value <= limit
                ok &= good
                print(f"prompt {n} ({L} tokens) layer {row['layer']} "
                      f"update, median over the {part} positions: "
                      f"{value:.5f}  limit {limit:.5f}  "
                      f"{'ok' if good else 'OVER'}")
        with jax.default_matmul_precision("highest"):
            last = ref.rms_norm(jnp.asarray(states[-1][L - 1:]),
                                params["final_norm"], config["rms_norm_eps"])
            head = np.asarray(last @ ref.head(params))
        entry["head_rel_l2"] = [float(e) for e in base.rel_l2(rows, head)]
        print(f"prompt {n} logit rows against the reference's tied head on "
              f"the engine's last residual stream (no limit): rel_l2 max "
              f"{max(entry['head_rel_l2']):.5f}")
        entry["reference_s"] = time.monotonic() - t
        if n < controls:
            caught = False
            for name, control in (("act_int4", {"act_quant": 4}),
                                  ("cache_int8", {"cache_int8": True})):
                if name == "act_int4" and not act_quant:
                    continue
                low = base.layer_by_layer(ref, params, config, states, L,
                                          act_quant, control)
                worst = max(r["prompt_median"] for r in low)
                entry[f"control_{name}"] = low
                caught |= worst > limit
                print(f"prompt {n} control {name} (reference one precision "
                      f"lower, against the reference): update median, "
                      f"worst layer {worst:.5f}  limit {limit:.5f}  "
                      f"{'not correct, as it must be' if worst > limit else 'passes: this limit cannot see it'}")
            ok &= caught
            entry["control_caught"] = caught
        out["prompts"].append(entry)
    out["ok"] = bool(ok)
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--traffic", required=True)
    parser.add_argument("--prompts", type=int, default=2)
    parser.add_argument("--decode", type=int, default=12)
    parser.add_argument("--controls", type=int, default=1,
                        help="prompts that also get the lower-precision controls")
    parser.add_argument("--tokens", default="",
                        help="comma-separated prompt lengths (default: drawn)")
    args = parser.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("compare_layers: no TPU here", file=sys.stderr)
        return 2
    out = compare(args.config, args.seed, traffic=args.traffic,
                  prompts=args.prompts, decode=args.decode,
                  controls=args.controls,
                  tokens=[int(t) for t in args.tokens.split(",") if t])
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    import os

    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)   # as run.py: no TPU runtime teardown after the step thread
