"""Production serving driver: DIANA-queued batched inference.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b --reduced \
        --requests 16 --slots 4
    PYTHONPATH=src python -m repro.launch.serve --arch recurrentgemma-2b \
        --no-reduced --requests 8 --slots 4 --prompt-len 16 --new-tokens 16

``--no-reduced`` serves the published full-width configuration.
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, list_archs
from repro.models import LM
from repro.runtime.compile_cache import setup_compile_cache
from repro.serving import InferenceRequest, ServingEngine


def main(argv=None):
    """Serve ``--requests`` random prompts; returns ``(engine, requests)``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-9b", choices=list_archs())
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction, default=True)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=8)
    args = ap.parse_args(argv)

    setup_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced).replace(remat=False)
    lm = LM(cfg)
    params = jax.jit(lm.init)(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    engine = ServingEngine(lm, params, num_slots=args.slots,
                           max_len=args.max_len,
                           quotas={"tenant-a": 100.0, "tenant-b": 100.0})
    reqs = []
    for i in range(args.requests):
        r = InferenceRequest(
            user=f"tenant-{'ab'[i % 2]}",
            prompt=rng.integers(0, cfg.vocab_size, args.prompt_len).astype(np.int32),
            max_new_tokens=args.new_tokens)
        reqs.append(r)
        engine.submit(r, now=float(i))
    t0 = time.time()
    stats = engine.run_until_drained()
    dt = time.time() - t0
    tokens = sum(len(r.generated) for r in reqs)
    print(f"served={stats.served}/{args.requests} batches={stats.batches} "
          f"decode_steps={stats.decode_steps} tokens={tokens} "
          f"({tokens / dt:.1f} tok/s wall)")
    return engine, reqs


if __name__ == "__main__":
    main()
