"""Serving driver: continuous batching over the jitted decode step.

    pip install -e .   # once
    python examples/serve_batched.py --arch llama3.2-1b
"""

import argparse
import time

import jax

from repro.launch.mesh import make_mesh
import numpy as np

from repro.configs import get_smoke
from repro.models import SINGLE_POD_PLAN
from repro.models import transformer as T
from repro.serve import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args()

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_smoke(args.arch)
    plan = SINGLE_POD_PLAN
    params, _ = T.init_params(jax.random.PRNGKey(0), cfg, plan)
    eng = ServeEngine(cfg, plan, mesh, params, slots=args.slots, s_max=128)

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, rng.integers(3, 9))
                    .astype(np.int32), max_new=args.max_new,
                    temperature=0.0 if i % 2 else 0.8)
            for i in range(args.requests)]
    for r in reqs:
        eng.submit(r)

    t0 = time.time()
    finished = eng.run_until_drained(max_ticks=10_000)
    dt = time.time() - t0
    toks = sum(len(r.out) for r in reqs)
    print(f"{len(finished)}/{len(reqs)} requests served, {toks} tokens "
          f"({dt:.1f}s, {toks/dt:.1f} tok/s on CPU, slots={args.slots})")
    for r in reqs[:4]:
        print(f"  req{r.rid}: prompt{list(r.prompt[:4])}… -> {r.out}")
    assert len(finished) == len(reqs) and all(r.done for r in finished)


if __name__ == "__main__":
    main()
