import os
os.environ["XLA_FLAGS"] = os.environ.get(
    "DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512"
)
# ^ MUST precede any jax import.

"""Dry-run of the PAPER'S technique on the production mesh: one DMTRL
communication round (local block-Gram SDCA + delta_b all-gather + Sigma
reduce) lowered and compiled at pod scale.

    PYTHONPATH=src python -m repro.launch.dryrun_dmtrl --mesh both

Configs: m=4096 tasks sharded over 'data' (the paper's workers), feature
dim d=8192 sharded over 'model' (block-Gram psums), and on the multi-pod
mesh each task's samples additionally split over 'pod'.
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dmtrl import DMTRLConfig
from repro.core.distributed import MeshAxes, make_distributed_round
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh
from repro.roofline.analysis import analyze_compiled

SDS = jax.ShapeDtypeStruct


def run(mesh_name: str, m: int, n_max: int, d: int, out_dir: str,
        H: int = 512, block: int = 128, tag: str = "",
        x_dtype=jnp.float32) -> dict:
    multi = mesh_name == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    axes = MeshAxes(
        data="data", model="model", pod="pod" if multi else None
    )
    cfg = DMTRLConfig(
        loss="hinge", lam=1e-4, local_iters=H, solver="block_gram",
        block_size=block,
    )
    rho = 4.0  # representative learned-Sigma value (Lemma 10 scale)
    round_fn = make_distributed_round(cfg, mesh, axes, m, n_max, d, rho)

    specs = (
        SDS((m, n_max, d), x_dtype),  # x
        SDS((m, n_max), jnp.float32),  # y
        SDS((m, n_max), jnp.float32),  # mask
        SDS((m,), jnp.int32),  # n
        SDS((m, n_max), jnp.float32),  # alpha
        SDS((m, d), jnp.float32),  # W
        SDS((m, m), jnp.float32),  # sigma rows
        SDS((2,), jnp.uint32),  # key
    )
    t0 = time.time()
    lowered = round_fn.lower(*specs)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    n_chips = int(np.prod(list(mesh.shape.values())))
    # useful flops: block-Gram per task per round:
    #   q,xr: 2*H*d*2 ; G: H*B*d... per block: 2*(B*d)*2 + B^2*d*2 ; r upd B*d*2
    nb = H // block
    per_task = nb * (2 * 2 * block * d + 2 * block * block * d + 2 * block * d)
    model_flops = float(m * per_task)
    terms = analyze_compiled(
        compiled,
        arch=f"dmtrl-m{m}-d{d}{tag}",
        shape=f"wstep-H{H}-B{block}",
        mesh_name=mesh_name,
        n_chips=n_chips,
        model_flops=model_flops,
        peak_flops=PEAK_FLOPS_BF16,
        hbm_bw=HBM_BW,
        ici_bw=ICI_BW,
    )
    rec = {"status": "ok", "lower_s": round(t_lower, 1),
           "compile_s": round(t_compile, 1), **terms.to_row()}
    rec["memory_analysis"] = (rec.get("memory_analysis") or "")[:2000]
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"dmtrl{tag}__wstep__{mesh_name}.json"), "w") as f:
        json.dump(rec, f, indent=1, default=str)
    print(f"== DMTRL W-step round / {mesh_name} ({n_chips} chips) ==")
    try:
        print(compiled.memory_analysis())
    except Exception as e:
        print("memory_analysis unavailable:", e)
    print(
        f"compute {terms.compute_s*1e3:.2f}ms  memory {terms.memory_s*1e3:.2f}ms  "
        f"collective {terms.collective_s*1e3:.2f}ms  dominant={terms.dominant}"
    )
    print("collectives:", terms.collective_breakdown)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--m", type=int, default=4096)
    ap.add_argument("--n-max", type=int, default=2048)
    ap.add_argument("--d", type=int, default=8192)
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--x-bf16", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--H", type=int, default=512)
    args = ap.parse_args()
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for mn in meshes:
        run(mn, args.m, args.n_max, args.d, args.out, H=args.H,
            tag=args.tag,
            x_dtype=jnp.bfloat16 if args.x_bf16 else jnp.float32)


if __name__ == "__main__":
    main()
