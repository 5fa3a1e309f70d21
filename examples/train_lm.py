"""End-to-end driver: train a ~50M-param qwen3-family model (scaled to
this 1-core host; --layers/--d-model scale it to 100M+) for a couple of
hundred steps on the synthetic bigram corpus, with the full production
substrate — AdamW, checkpoint/restart, preemption handling, straggler
watchdog, metrics JSONL.  The CE must drop by >=0.5 nats and approach the
chain's conditional entropy (crossing the uniform baseline).

  PYTHONPATH=src python examples/train_lm.py [--steps 300] [--resume]
"""
import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES, get
from repro.data.pipeline import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as lm
from repro.optim import adamw
from repro.train.loop import LoopConfig, TrainLoop


def build_cfg(layers=8, d_model=768):
    # ~53M params at the defaults; 12 x 896 gives ~100M on a bigger host
    return get("qwen3-1.7b").reduced().replace(
        n_layers=layers, d_model=d_model, n_heads=d_model // 64,
        n_kv_heads=max(d_model // 192, 1), d_ff=int(d_model * 8 // 3),
        vocab_size=4096, head_dim=None)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--out", default="runs/train_lm")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = build_cfg()
    params = lm.init(cfg, jax.random.PRNGKey(0))
    n = lm.param_count(params)
    print(f"arch={cfg.name}(reduced) params={n/1e6:.1f}M")

    oc = adamw.AdamWConfig(peak_lr=1e-3, warmup_steps=20,
                           total_steps=args.steps, weight_decay=0.01)
    opt = adamw.init(params, oc)
    # a 512-state bigram chain: enough structure to show clear learning
    # inside a few hundred small-batch steps on this host
    pipe = SyntheticLM(cfg, SHAPES["train_4k"], seed=0,
                       batch_override=args.batch, seq_override=args.seq,
                       active_vocab=512)

    @jax.jit
    def step_fn(p, s, batch):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        (loss, mets), g = jax.value_and_grad(
            lambda pp: lm.loss_fn(pp, batch, cfg), has_aux=True)(p)
        p2, s2, om = adamw.apply(p, g, s, oc)
        return p2, s2, dict(mets, **om)

    loop = TrainLoop(
        LoopConfig(total_steps=args.steps, ckpt_every=100, log_every=10,
                   out_dir=args.out),
        step_fn, params, opt, pipe)
    out = loop.run()
    print(json.dumps({k: round(v, 4) if isinstance(v, float) else v
                      for k, v in out.items()}))

    lines = [json.loads(l) for l in
             (Path(args.out) / "metrics.jsonl").read_text().splitlines()]
    first, last = lines[0]["ce"], lines[-1]["ce"]
    print(f"ce: {first:.3f} -> {last:.3f} "
          f"(uniform baseline {np.log(pipe.active_vocab):.3f})")
    assert last < first - 0.5, "loss did not improve"
    print("train_lm OK")


if __name__ == "__main__":
    main()
