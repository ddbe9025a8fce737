"""Serving launcher: geo-distributed BPRR serving of a (reduced) arch.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_1b \
        --requests 5 --algorithm proposed
"""
import argparse

import numpy as np
import jax

from repro.configs import get_reduced_config
from repro.core import GB, LLMSpec, Problem, ServerSpec, Workload
from repro.launch.compile_cache import use_compile_cache
from repro.models import init_params
from repro.serving import GeoServingSystem, generate


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_2_1b")
    ap.add_argument("--algorithm", default="proposed",
                    choices=["proposed", "petals"])
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=12)
    ap.add_argument("--servers", type=int, default=4)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_reduced_config(args.arch)
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    llm = LLMSpec(cfg.name, cfg.n_layers, block_bytes=50.0,
                  cache_bytes_per_token=0.5)
    rng = np.random.RandomState(0)
    servers = [ServerSpec(j, mem_bytes=50.0 * cfg.n_layers * 2,
                          tau=0.005 * (1 + j % 3))
               for j in range(args.servers)]
    rtt = 0.01 + 0.02 * rng.rand(1, args.servers)
    problem = Problem(llm, servers, 1, rtt, 3 * rtt,
                      workload=Workload(8, args.new_tokens))
    system = GeoServingSystem(cfg, params, problem,
                              algorithm=args.algorithm,
                              max_new_tokens=args.new_tokens + 4)
    print(f"{args.algorithm} placement: a={system.placement.a} "
          f"m={system.placement.m}")
    for r in range(args.requests):
        toks = rng.randint(2, cfg.vocab_size, 8)
        out, vt = generate(system, toks, args.new_tokens)
        print(f"req {r}: virtual {vt:.3f}s  tokens {out[8:8+6]}...")


if __name__ == "__main__":
    main()
