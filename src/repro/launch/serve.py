"""End-to-end serving driver: static batching or continuous batching.

Static (the classic fixed-batch baseline):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --requests 8 --prompt-len 32 --max-new 16

Continuous (slot map + admission between decode steps) on a MIXED-length
workload, with the static engine run on the same workload for comparison —
the ``slot_steps`` line is the paper's load-imbalance argument in serving
currency (decode steps x batch slots):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --continuous --requests 16 --arrival-rate 2
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry as R
from repro.models.registry import VLM_PATCHES
from repro.serve import (
    ContinuousEngine,
    Request,
    ServeEngine,
    engine_record,
    generate_bucketed,
    make_mixed_workload,
)


def _extra_inputs(cfg, args, rng):
    if cfg.family == "encdec":
        return {"frames": rng.standard_normal(
            (args.batch, args.prompt_len, cfg.d_model)).astype(np.float32)}
    if cfg.family == "vlm":
        P = min(VLM_PATCHES, args.prompt_len // 2)
        return {"patches": rng.standard_normal(
            (args.batch, P, cfg.d_model)).astype(np.float32)}
    return None


def _prompt_lens(cfg, args) -> list[int]:
    """Two prefill buckets, except families with fixed-shape side inputs
    (enc-dec frames, VLM patches) which keep one prompt length — their
    imbalance then comes from the output lengths alone."""
    if cfg.family in ("encdec", "vlm"):
        return [args.prompt_len]
    return [max(args.prompt_len // 2, 4), args.prompt_len]


def _summarize(tag: str, reqs: list[Request], stats: dict, wall: float) -> dict:
    rec = engine_record(reqs, stats, wall)
    line = (f"{tag}: {rec['requests']} requests, {rec['new_tokens']} tokens "
            f"in {rec['wall_s']:.2f}s ({rec['tok_s']} tok/s), "
            f"decode_steps={rec['decode_steps']} slot_steps={rec['slot_steps']}")
    if "ttft_mean_s" in rec:
        line += (f", ttft mean={rec['ttft_mean_s']*1e3:.0f}ms "
                 f"p99={rec['ttft_p99_s']*1e3:.0f}ms")
    print(line)
    return rec


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--continuous", action="store_true",
                   help="continuous batching on a mixed-length workload, "
                        "with a static-batching comparison run")
    p.add_argument("--arrival-rate", type=float, default=0.0,
                   help="requests per decode step (0 = all queued up front); "
                        "continuous mode only")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--trace-dir", default=None,
                   help="write a Perfetto-loadable trace JSON per process "
                        "(repro.round/repro.prefill/repro.decode spans; "
                        "continuous mode)")
    args = p.parse_args(argv)
    enable_compile_cache()

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    api = R.build(cfg)
    params = api.init(jax.random.PRNGKey(args.seed))
    capacity = args.prompt_len + args.max_new + 1
    if cfg.family == "vlm":
        # the VLM frontend prepends patch rows to the decode context
        capacity += min(VLM_PATCHES, args.prompt_len // 2)
    rng = np.random.default_rng(args.seed)
    extra = _extra_inputs(cfg, args, rng)

    if args.continuous:
        reqs = make_mixed_workload(
            cfg.vocab_size, args.requests, _prompt_lens(cfg, args),
            args.max_new, rng, arrival_rate=args.arrival_rate,
        )
        clone = [Request(prompt=r.prompt.copy(), max_new_tokens=r.max_new_tokens,
                         eos_id=r.eos_id) for r in reqs]

        tracer = None
        if args.trace_dir:
            from repro.obs.trace import Tracer

            tracer = Tracer()
        cont = ContinuousEngine(api, batch_size=args.batch, capacity=capacity,
                                temperature=args.temperature, seed=args.seed,
                                tracer=tracer)
        t0 = time.perf_counter()
        cont.serve(params, reqs, extra_inputs=extra)
        _summarize("continuous", reqs, cont.stats, time.perf_counter() - t0)
        if tracer is not None:
            from repro.obs.export import write_trace_dir

            print("trace:", write_trace_dir(tracer, args.trace_dir,
                                            basename="serve"))

        static = ServeEngine(api, batch_size=args.batch, capacity=capacity,
                             temperature=args.temperature, seed=args.seed)
        t0 = time.perf_counter()
        generate_bucketed(static, params, clone, extra_inputs=extra)
        _summarize("static    ", clone, static.stats, time.perf_counter() - t0)

        c, s = cont.stats["slot_steps"], static.stats["slot_steps"]
        print(f"slot_steps: continuous={c} static={s} "
              f"({s / max(c, 1):.2f}x fewer slot-seconds)")
        if c >= s:
            # a degenerate workload (e.g. a single request) cannot be
            # refilled, so slot refill has nothing to win — report it
            # cleanly instead of tracebacking
            raise SystemExit(
                f"continuous batching did not beat static on this workload "
                f"({c} vs {s} slot-steps); mixed-length workloads with more "
                f"requests than --batch are where refill pays"
            )
        return

    reqs = [
        Request(
            prompt=rng.integers(0, cfg.vocab_size, args.prompt_len, dtype=np.int32),
            max_new_tokens=args.max_new,
        )
        for _ in range(args.requests)
    ]
    engine = ServeEngine(api, batch_size=args.batch, capacity=capacity,
                         temperature=args.temperature, seed=args.seed)
    t0 = time.perf_counter()
    for i in range(0, len(reqs), args.batch):
        batch = reqs[i : i + args.batch]
        engine.generate(params, batch, extra_inputs=extra)
        print(f"batch {i // args.batch}: "
              + "; ".join(str(r.out_tokens[:8]) for r in batch))
    _summarize("static", reqs, engine.stats, time.perf_counter() - t0)


if __name__ == "__main__":
    main()
