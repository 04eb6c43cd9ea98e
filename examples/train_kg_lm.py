"""End-to-end driver: evolving KG -> interest-filtered replica -> LM training.

The full production loop (DESIGN.md §4): the synthetic source publishes
changesets, the iRap subscription keeps the Football replica consistent, the
verbalizer turns replica triples into token streams, and the fault-tolerant
Trainer (checkpoint/restart, straggler detection) fits a decoder LM on them
— refreshing the pipeline whenever the replica changes.

    PYTHONPATH=src python examples/train_kg_lm.py --steps 60
    PYTHONPATH=src python examples/train_kg_lm.py --steps 300 --width 768 \
        --layers 12   # ~100M-param configuration
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import numpy as np

from benchmarks.common import FOOTBALL, default_generator, football_caps
from repro.compile_cache import enable_compile_cache
from repro.core import IrapEngine
from repro.data import ReplicaTokenPipeline, Verbalizer
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.models.config import ModelConfig
from repro.optim import AdamW, cosine_warmup
from repro.runtime import Trainer, TrainerConfig


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--width", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=4096)
    ap.add_argument("--ckpt-dir", default="/tmp/irap_train_ckpt")
    ap.add_argument("--refresh-every", type=int, default=25,
                    help="apply one changeset + refresh pipeline every N steps")
    ap.add_argument("--inject-failure-at", type=int, default=None)
    args = ap.parse_args()

    cfg = ModelConfig(
        name="kg-lm", family="dense", n_layers=args.layers,
        d_model=args.width, n_heads=max(4, args.width // 64),
        n_kv_heads=max(2, args.width // 128), d_head=64,
        d_ff=args.width * 4, vocab=args.vocab,
    )
    api = build_model(cfg)
    print(f"model: {cfg.n_params/1e6:.1f} M params")

    # data plane: generator -> subscription -> verbalizer -> pipeline
    gen = default_generator(seed=11, scale=1.0)
    gen.initial_dump()
    engine = IrapEngine(gen.dict)
    sub = engine.register_interest(
        FOOTBALL, football_caps(),
        initial_target=gen.slice_for(
            lambda t: t[0].startswith(("dbr:Athlete", "dbr:Team"))),
    )
    verb = Verbalizer(vocab=args.vocab, dictionary=gen.dict)
    pipe = ReplicaTokenPipeline(verb, batch_size=args.batch, seq_len=args.seq)
    pipe.refresh(sub.tau)
    print(f"replica τ: {int(sub.tau.n)} triples")

    state = {"n": 0}

    def data():
        while True:
            state["n"] += 1
            if state["n"] % args.refresh_every == 0:
                d_np, a_np = gen.changeset()
                out = sub.apply(d_np, a_np)
                pipe.refresh(sub.tau)
                print(f"  [changeset] +{int(out.a.n)} interesting, "
                      f"τ={int(sub.tau.n)} — pipeline refreshed")
            yield next(pipe)

    opt = AdamW(
        learning_rate=cosine_warmup(3e-3, 20, args.steps),
        weight_decay=0.01, max_grad_norm=1.0,
    )

    def init_state():
        params = api.init(jax.random.key(0))
        return params, opt.init(params)

    tr = Trainer(
        make_train_step(api, opt), init_state, data(),
        TrainerConfig(ckpt_dir=args.ckpt_dir, ckpt_every=20),
    )
    print(f"starting at step {tr.step}")
    t0 = time.time()
    hist = tr.run(args.steps, inject_failure_at=args.inject_failure_at)
    dt = time.time() - t0
    print(f"\ntrained {len(hist)} steps in {dt:.1f}s "
          f"({dt/len(hist):.2f} s/step)")
    print(f"loss: {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")
    tr.save()


if __name__ == "__main__":
    main()
