"""Serving driver: batched continuous-batching engine over a smoke model.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        [--requests 8] [--batch 4] [--max-seq 128] [--int8]
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_smoke
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.models.params import materialize
from repro.serve.engine import Request, ServeEngine


def main():
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="llama3.2-1b")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--max-seq", type=int, default=128)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--int8", action="store_true")
    a = p.parse_args()

    cfg = get_smoke(a.arch)
    params = materialize(lm.param_defs(cfg), jax.random.key(0))
    eng = ServeEngine(cfg, params, batch_size=a.batch, max_seq=a.max_seq,
                      quantize=a.int8)
    rng = np.random.default_rng(0)
    t0 = time.monotonic()
    for uid in range(a.requests):
        plen = int(rng.integers(4, 12))
        eng.submit(Request(uid=uid,
                           prompt=rng.integers(1, cfg.vocab_size,
                                               plen).astype(np.int32),
                           max_new_tokens=a.max_new))
    done = eng.run()
    dt = time.monotonic() - t0
    toks = sum(len(r.out_tokens) for r in done)
    print(f"{len(done)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks/dt:.1f} tok/s, int8={a.int8})")
    for r in sorted(done, key=lambda r: r.uid)[:4]:
        print(f"  req {r.uid}: prompt[{len(r.prompt)}] -> {r.out_tokens}")


if __name__ == "__main__":
    main()
