"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

By default it drives the *smoke-scale* config end-to-end with the full
production stack (sharded state, deterministic pipeline, fault-tolerant
``Trainer``, checkpointing).  ``--no-smoke`` trains the published widths: on
one four-chip host, ``--no-smoke --model-axis 4`` splits internlm2-1.8b's
parameters and Adam state over the chips (``chip_smoke.py --chips 4``
runs that path).  On a TPU fleet the mesh comes from ``--mesh`` and
jax.distributed initialization (one process per host) — everything else
is identical.
"""
from __future__ import annotations

import argparse
import json
import os


from repro.configs import ARCH_NAMES, get_arch
from repro.configs.base import ShapeSpec
from repro.data.arch_data import ArchSyntheticDataset
from repro.dist.sharding import get_profile
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.optim import AdamWConfig
from repro.optim.schedule import linear_warmup_cosine
from repro.train.driver import Trainer, TrainerConfig


def build_trainer(arch, *, steps: int, batch: int, seq: int, lr: float,
                  accum: int = 1, mesh: str = "host", model_axis: int = 1,
                  ckpt_dir: str = "results/ckpt", ckpt_interval: int = 25,
                  moment_dtype: str = "f32", seed: int = 0):
    """The launcher's :class:`Trainer`: ``mesh="host"`` spans this host's
    devices with ``model_axis`` of them on the tensor-parallel axis.
    Returns ``(trainer, dataset)``."""
    if mesh == "host":
        device_mesh = make_host_mesh(model=model_axis)
        multi_pod = False
    else:
        multi_pod = mesh == "multi-pod"
        device_mesh = make_production_mesh(multi_pod=multi_pod)
    profile = get_profile(arch.profile, multi_pod=multi_pod)

    shape = ShapeSpec("cli_train", seq_len=seq, global_batch=batch,
                      kind="train")
    data = ArchSyntheticDataset(arch, shape, seed=seed)
    opt = AdamWConfig(moment_dtype=moment_dtype)
    sched = linear_warmup_cosine(lr, steps // 10 + 1, steps)
    trainer = Trainer(
        arch, data, device_mesh, profile, opt, sched,
        TrainerConfig(total_steps=steps,
                      ckpt_dir=os.path.join(ckpt_dir, arch.name),
                      ckpt_interval=ckpt_interval,
                      accum=accum, seed=seed,
                      multi_pod=multi_pod))
    return trainer, data


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="internlm2-1.8b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="reduced config (CPU scale); --no-smoke for full")
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--mesh", default="host",
                    choices=("host", "single-pod", "multi-pod"))
    ap.add_argument("--model-axis", type=int, default=1,
                    help="host mesh: devices on the tensor-parallel axis "
                         "(4 splits internlm2-1.8b's training state over "
                         "a four-chip host)")
    ap.add_argument("--ckpt-dir", default="results/ckpt")
    ap.add_argument("--ckpt-interval", type=int, default=25)
    ap.add_argument("--moment-dtype", default="f32",
                    choices=("f32", "bf16", "int8"))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    arch = get_arch(args.arch, smoke=args.smoke)
    trainer, _ = build_trainer(
        arch, steps=args.steps, batch=args.batch, seq=args.seq, lr=args.lr,
        accum=args.accum, mesh=args.mesh, model_axis=args.model_axis,
        ckpt_dir=args.ckpt_dir, ckpt_interval=args.ckpt_interval,
        moment_dtype=args.moment_dtype, seed=args.seed)
    out = trainer.run()
    print(json.dumps({"arch": arch.name,
                      "steps": out["final_step"],
                      "first_loss": out["losses"][0],
                      "final_loss": out["final_loss"],
                      "stragglers": out["stragglers"]}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
