"""Serving launcher: thin CLI over the continuous-batching engine
(``repro.serve``) with warm, separated metrics — prefill latency and
per-decode-token latency are reported independently (compile excluded by
an explicit warmup pass), never folded into one number.

  PYTHONPATH=src python -m repro.launch.serve --arch gpt2-small --smoke \
      --slots 4 --requests 16 --max-len 64
"""
import argparse
import sys


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=100.0,
                    help="Poisson arrival rate (requests/s)")
    ap.add_argument("--max-len", type=int, default=64,
                    help="KV-cache capacity (prompt + generation)")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)

    from ..runtime.device import use_compile_cache
    use_compile_cache()
    import jax

    from ..configs import get_config, smoke_config
    from ..models import model_init
    from ..serve import DenseServeModel, ServeEngine, synthetic_requests

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params, _ = model_init(cfg, jax.random.key(0))
    prompt_lens = tuple(p for p in (8, 12, 16, 24) if p < args.max_len)
    engine = ServeEngine(DenseServeModel(cfg, params, args.max_len),
                         num_slots=args.slots)
    engine.warmup(prompt_lens)
    reqs = synthetic_requests(cfg, args.requests, seed=0, rate=args.rate,
                              prompt_lens=prompt_lens,
                              steps_range=(4, max(4, args.max_len // 4)))
    report = engine.run(reqs)
    m = report.as_dict()
    print(f"[serve] {cfg.name}: {m['requests']} requests, "
          f"{m['total_tokens']} tokens, {args.slots} slots")
    print(f"  prefill         {m['prefill_ms_mean']:8.2f} ms (warm, mean)")
    print(f"  decode          {m['decode_ms_per_token_mean']:8.2f} ms/token "
          f"(warm, mean)")
    print(f"  latency (host clock) request p50={m['p50_ms']:.1f} "
          f"p99={m['p99_ms']:.1f} ms, first token "
          f"p50={m['ttft_p50_ms']:.1f} p99={m['ttft_p99_ms']:.1f} ms, "
          f"inter-token p50={m['itl_p50_ms']:.2f} "
          f"p99={m['itl_p99_ms']:.2f} ms")
    print(f"  throughput      {m['tokens_per_s']:8.1f} tokens/s")
    print("sample:", report.records[0].tokens[:8])
    return m


if __name__ == "__main__":
    main()
    sys.exit(0)
