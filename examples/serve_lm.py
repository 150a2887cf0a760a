"""Serving example: batched decode with continuous slot refill.

    PYTHONPATH=src python examples/serve_lm.py
"""

import sys

sys.path.insert(0, "src")

import jax

from repro import compile_cache
from repro.configs import get_arch
from repro.models import build_model
from repro.serve import DecodeEngine, Request


def main():
    compile_cache.enable()
    cfg = get_arch("zamba2_1p2b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    engine = DecodeEngine(model, params, batch_size=4, max_seq=64)
    for uid in range(8):  # 8 requests through 4 slots -> continuous batching
        engine.submit(Request(uid=uid, prompt=[1 + uid % 5, 2, 3],
                              max_new_tokens=8))
    done = engine.run_until_done()
    for req in sorted(done, key=lambda r: r.uid):
        print(f"request {req.uid}: prompt={req.prompt} -> {req.output}")
    assert len(done) == 8
    print("served 8 requests through 4 decode slots ✓")


if __name__ == "__main__":
    main()
