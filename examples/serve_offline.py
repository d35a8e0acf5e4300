"""End-to-end serving driver (deliverable b): batched requests against a
small model with continuous batching, chunked prefill, discrete batching,
async EOS and KV offload — the paper's full serving path.

    PYTHONPATH=src python examples/serve_offline.py [--requests 24]
"""
import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.runtime import device_label
from repro.models import model
from repro.serving.config import EngineConfig
from repro.serving.engine import ServeEngine
from repro.serving.request import Request


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny-toy")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    params = model.init(cfg, jax.random.PRNGKey(args.seed))
    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=8, max_len=128, discrete_sizes=(64, 32, 16, 8),
        avg_decode_len=10))

    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        plen = int(rng.integers(4, 48))
        eng.submit(Request(
            rid=i, prompt=list(rng.integers(0, cfg.vocab_size, size=plen)),
            max_new_tokens=int(rng.integers(4, 24)),
            eos_id=int(rng.integers(0, cfg.vocab_size)) if i % 3 == 0 else None))

    done = eng.run()
    st = eng.stats
    print(f"finished {len(done)}/{args.requests} in {st.iterations} iterations")
    print(f"tokens: {st.prefill_tokens} prefill + {st.decode_tokens} decode "
          f"= {st.total_tokens} @ {st.throughput:.1f} tok/s on {device_label()}")
    print(f"dense-batch histogram (discrete batching): "
          f"{dict(sorted(st.dense_batch_hist.items()))}")
    kv = eng.kv.stats
    print(f"KV: {kv.aggregated_copies} offloads, "
          f"{kv.offload_bytes/1e6:.2f} MB D2H (page-aggregated), "
          f"host pool {kv.host_bytes/1e6:.2f} MB")


if __name__ == "__main__":
    main()
