"""Serving launcher: continuous-batching decode over a registered architecture.

CPU-capable with --smoke (reduced config); on hardware the same step functions
run over the production mesh with the shardings from launch/steps.py.

The default path drives `repro.serve.engine.DecodeEngine` — a slotted
KV-cache with prefill-into-free-slot admission (DESIGN.md §15) — over a
batch of requests with staggered arrivals (``--stagger`` steps apart), the
workload the old single-stream loop could only serve lock-step.
``--single-stream`` keeps the legacy whole-batch `generate` loop for
comparison; both report throughput on **materialized** outputs
(``block_until_ready``, so tok/s measures compute, not async dispatch) as a
wall number (incl. compile) next to a compile-excluded warm number.

Decode energy is reported two ways: *measured* joules/token from the
per-stage engine microbenchmarks (`repro.serve.microbench` →
``DecodeCostModel.from_microbench`` at the nominal device wattage) next to
the *analytic* ``from_params`` pricing (~2*N FLOPs/token) the battery-gated
serving fleet historically debited (`repro.serve`).

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b --smoke \\
      --batch 6 --slots 4 --stagger 2 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import functools
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.energy.costs import DecodeCostModel
from repro.launch.cache import enable_compile_cache
from repro.models import get_model


@functools.lru_cache(maxsize=32)
def _jitted_steps(prefill_fn, decode_fn, cache_len: int, ring: bool, window):
    """Jitted (prefill, decode) pair, cached on the model's bound step
    functions + serving shape knobs: repeat `generate` calls on the same
    model hit the jit cache instead of rebuilding per-call lambdas (the
    recompile-every-invocation anti-pattern `_run_fleet_scan` documents)."""
    prefill = jax.jit(partial(prefill_fn, cache_len=cache_len, window=window))
    decode = jax.jit(partial(decode_fn, ring=ring, window=window))
    return prefill, decode


def generate(model, params, prompt, gen_steps: int, cache_len: int,
             ring: bool = False, window=None, greedy: bool = True,
             temperature: float = 1.0, rng=None):
    """Batched greedy or temperature-sampled generation (single-stream path).

    prompt: dict with (B, S) int32 ``tokens`` (+ modality extras).  With
    ``greedy=False`` each step draws from ``softmax(logits / temperature)``
    (requires ``rng`` and ``temperature > 0``); ``greedy=True`` ignores
    temperature.  Returns (B, ``gen_steps``) tokens — exactly the count the
    launcher divides throughput and J/token by (the first comes from the
    prefill logits, the rest from ``gen_steps - 1`` decode steps).
    """
    if not greedy and rng is None:
        raise ValueError("sampling (greedy=False) requires an rng key")
    if not greedy and not temperature > 0.0:
        # logits/0 would silently sample the first +inf-logit token
        raise ValueError(
            f"temperature must be > 0 for sampling (got {temperature}); "
            f"use greedy=True for argmax decoding")
    B, S = prompt["tokens"].shape
    if gen_steps < 1:
        return jnp.zeros((B, 0), jnp.int32)
    prefill, decode = _jitted_steps(model.prefill, model.decode_step,
                                    cache_len, ring, window)

    logits, cache = prefill(params, prompt)
    logits = logits[:, -1] if logits.ndim == 3 else logits

    def pick(logits, rng):
        if greedy:
            return jnp.argmax(logits, -1).astype(jnp.int32), rng
        rng, k = jax.random.split(rng)
        tok = jax.random.categorical(k, logits / temperature)
        return tok.astype(jnp.int32), rng

    tok, rng = pick(logits, rng)
    out = [tok]
    for i in range(gen_steps - 1):
        logits, cache = decode(params, tok, cache, jnp.int32(S + i))
        tok, rng = pick(logits, rng)
        out.append(tok)
    return jnp.stack(out, axis=1)


def _decode_shape(cfg, prompt_len: int, gen: int):
    """(cache_len, ring, window) under the decode-shape policy (DESIGN.md
    §5): full cache sized to the workload, ring = the arch's window."""
    cache_len, ring, window = prompt_len + gen + 1, False, None
    if cfg.family == "hybrid":
        cache_len, ring = cfg.local_window, True
    if cfg.sliding_window:
        cache_len, ring, window = cfg.sliding_window, True, cfg.sliding_window
    return cache_len, ring, window


def _make_prompt(cfg, rng, batch: int, prompt_len: int) -> dict:
    prompt = {"tokens": jax.random.randint(rng, (batch, prompt_len), 0,
                                           cfg.vocab_size)}
    if cfg.family == "vlm":
        nv = min(cfg.vision_tokens, prompt_len)
        prompt["vision_embeds"] = jax.random.normal(
            rng, (batch, nv, cfg.d_model), dtype=jnp.dtype(cfg.dtype))
    if cfg.family == "encdec":
        prompt["frames"] = jax.random.normal(
            rng, (batch, cfg.encoder_seq, cfg.d_model),
            dtype=jnp.dtype(cfg.dtype))
    return prompt


def run_engine(model, params, prompts, *, gen: int, slots: int,
               cache_len: int, stagger: int = 0, ring: bool = False,
               window=None, greedy: bool = True, temperature: float = 1.0,
               rng=None):
    """One `DecodeEngine` pass over ``prompts`` (each a dict of one request's
    unbatched ``tokens`` (S,) + modality extras; lengths may differ),
    request ``i`` arriving at step ``i * stagger`` with a budget of ``gen``
    tokens.  Returns ``({i: Finished}, wall seconds, engine)``.  Output rows
    are materialized by construction — the engine fetches each finished
    slot's row before reclaiming it."""
    from repro.serve.engine import DecodeEngine, EngineConfig, Request

    reqs = [Request(rid=i, tokens=np.asarray(pr["tokens"]), max_new=gen,
                    extras={k: np.asarray(v) for k, v in pr.items()
                            if k != "tokens"} or None)
            for i, pr in enumerate(prompts)]
    arrivals = [i * stagger for i in range(len(reqs))]
    engine = DecodeEngine(model, params,
                          EngineConfig(slots=slots, cache_len=cache_len,
                                       max_new=gen, ring=ring, window=window,
                                       greedy=greedy,
                                       temperature=temperature),
                          rng=rng)
    t0 = time.perf_counter()
    done = engine.run(reqs, arrivals=arrivals)
    return done, time.perf_counter() - t0, engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-1.3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests in the workload")
    ap.add_argument("--slots", type=int, default=4,
                    help="engine running-batch width (cache slots)")
    ap.add_argument("--stagger", type=int, default=2,
                    help="steps between request arrivals (continuous-"
                         "batching admission pressure; 0 = all at once)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sample", action="store_true",
                    help="temperature-sample instead of greedy argmax")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--single-stream", action="store_true",
                    help="legacy whole-batch generate loop instead of the "
                         "slotted engine")
    ap.add_argument("--skip-microbench", action="store_true",
                    help="skip the per-stage microbenchmark (faster smoke)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = get_model(cfg)
    if model.decode_step is None:
        raise SystemExit(f"{cfg.name} has no decode path")
    # independent streams: params init, prompt draw, and sampling must not
    # share a key (a shared key correlates the sampled continuation with the
    # prompt/params draw)
    k_params, k_prompt, k_sample = jax.random.split(
        jax.random.PRNGKey(args.seed), 3)
    params = model.init_params(k_params)

    B, S = args.batch, args.prompt_len
    prompt = _make_prompt(cfg, k_prompt, B, S)
    cache_len, ring, window = _decode_shape(cfg, S, args.gen)

    mode = (f"sampled@T={args.temperature}" if args.sample else "greedy")
    if args.single_stream:
        def run():
            toks = generate(model, params, prompt, args.gen, cache_len,
                            ring=ring, window=window, greedy=not args.sample,
                            temperature=args.temperature, rng=k_sample)
            return jax.block_until_ready(toks)  # time compute, not dispatch

        t0 = time.perf_counter()
        toks = np.asarray(run())
        wall = time.perf_counter() - t0
        t0 = time.perf_counter()
        toks = np.asarray(run())
        warm = time.perf_counter() - t0
        path = "single-stream"
        engine = None
    else:
        prompts = [{k: v[i] for k, v in prompt.items()} for i in range(B)]

        def run():
            done, dt, engine = run_engine(
                model, params, prompts, gen=args.gen, slots=args.slots,
                cache_len=cache_len, stagger=args.stagger, ring=ring,
                window=window, greedy=not args.sample,
                temperature=args.temperature, rng=k_sample)
            return np.stack([done[i].tokens for i in range(B)]), dt, engine

        toks, wall, engine = run()
        # second pass hits the engine's compiled-fns cache -> warm number
        toks, warm, engine = run()
        path = (f"engine[slots={args.slots} stagger={args.stagger} "
                f"inserts={engine.stats['inserts']} "
                f"steps={engine.stats['steps']}]")

    # the token count and the throughput denominator must agree: generate
    # and the engine both return exactly `gen` tokens per request
    n_tokens = toks.shape[0] * toks.shape[1]
    assert toks.shape == (B, args.gen), (toks.shape, (B, args.gen))
    print(f"arch={cfg.name} batch={B} prompt={S} generated={args.gen} "
          f"({mode}, {path})")
    print("tokens[0]:", toks[0])
    print(f"{n_tokens / wall:.1f} tok/s (wall, incl. compile)   "
          f"{n_tokens / warm:.1f} tok/s (warm, compile-excluded)")

    # decode-path energy: what this generation debits an edge battery —
    # analytic 2N-FLOPs pricing, plus the measured per-stage figure
    cost = DecodeCostModel.from_params(cfg.num_active_params())
    per_request = float(cost.request_cost(S, args.gen))
    total_j = B * per_request
    print(f"energy (analytic, nominal edge device): "
          f"{total_j / n_tokens:.3e} J/token, {per_request:.3e} J/request "
          f"({B} requests, {total_j:.3e} J total)")
    if not args.skip_microbench:
        from repro.serve.microbench import engine_microbench, measured_cost
        rec = engine_microbench(model, params, slots=args.slots,
                                prompt_len=S, gen=args.gen,
                                cache_len=cache_len, ring=ring,
                                window=window, reps=3, seed=args.seed)
        mcost = measured_cost(rec)
        mreq = float(mcost.request_cost(S, args.gen))
        print(f"energy (measured microbench @ {rec['device_watts']:.1f} W "
              f"host proxy): {float(mcost.joules_per_decode_step):.3e} "
              f"J/token decode, {mreq:.3e} J/request  "
              f"[prefill {rec['prefill_tok_s']:.0f} tok/s, decode step "
              f"{rec['decode_step_ms']:.2f} ms, insert "
              f"{rec['insert_ms']:.2f} ms]")


if __name__ == "__main__":
    main()
