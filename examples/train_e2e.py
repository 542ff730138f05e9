"""End-to-end training driver over the Lustre substrate.

Trains a small transformer (default ~27M params; --large for ~110M) for a
few hundred steps with:
  * the token corpus striped across OSTs (data pipeline),
  * parity-coded striped checkpoints every N steps,
  * an OST node failure injected mid-run (transparent failover),
  * a simulated trainer death + resume from the latest checkpoint.

Run:  PYTHONPATH=src python examples/train_e2e.py [--steps 200] [--large]
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

from repro.core import LustreCluster                       # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.config import ModelConfig, RunConfig     # noqa: E402
from repro.train.trainer import Trainer, TrainerConfig     # noqa: E402


def model_cfg(large: bool) -> ModelConfig:
    if large:   # ~110M params
        return ModelConfig(name="e2e-110m", family="transformer",
                           n_layers=12, d_model=768, n_heads=12,
                           n_kv_heads=4, head_dim=64, d_ff=2048,
                           vocab=8192)
    return ModelConfig(name="e2e-27m", family="transformer", n_layers=8,
                       d_model=448, n_heads=8, n_kv_heads=4, head_dim=56,
                       d_ff=1344, vocab=4096)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--large", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()
    enable_compile_cache()

    cluster = LustreCluster(osts=4, mdses=1, clients=2, ost_failover=True,
                            commit_interval=64)
    cfg = TrainerConfig(
        model=model_cfg(args.large),
        rc=RunConfig(seq_len=args.seq, global_batch=args.batch,
                     kind="train", attn_impl="ref"),
        n_steps=args.steps, ckpt_every=max(10, args.steps // 10),
        dataset_seqs=4096, n_writers=2, parity=True)

    n = cfg.model.n_params
    print(f"model: {cfg.model.name} ({n/1e6:.1f}M params), "
          f"{args.steps} steps, batch {args.batch} x seq {args.seq}")

    half = args.steps // 2
    t0 = time.time()
    tr = Trainer(cluster, cfg)
    tr.run(half, fail_at={half // 2: lambda c: c.fail_node("ost1")})
    print(f"first {half} steps done (ost1 killed at {half//2}): "
          f"loss {tr.metrics[0]['loss']:.3f} -> {tr.metrics[-1]['loss']:.3f}")
    print("checkpoints:", tr.ckpt.steps())

    # trainer dies; a new one resumes from the latest complete checkpoint
    del tr
    tr2 = Trainer.resume(cluster, cfg)
    print(f"resumed at step {tr2.step}")
    tr2.run(args.steps - tr2.step)
    dt = time.time() - t0
    print(f"final loss {tr2.metrics[-1]['loss']:.4f} at step {tr2.step} "
          f"({dt:.0f}s wall, {cluster.now:.1f}s virtual-storage time)")
    st = cluster.stats
    print("storage: wrote", st.bytes.get("ost.write", 0) >> 20, "MiB,",
          "read", st.bytes.get("ost.read", 0) >> 20, "MiB,",
          st.counters.get("rpc.timeout", 0), "timeouts,",
          st.counters.get("rpc.replay", 0), "replays")


if __name__ == "__main__":
    main()
