import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Production-mesh dry-run of the PAPER'S OWN workload: a batched stream of
# 180-bit x 4096-coefficient modular polynomial multiplications (the cloud
# HE-evaluation serving shape), plus the BFV ct x pt inference step.
#
#   RNS channels (t=6) -> `model` axis (the paper's t parallel datapaths
#   ARE model parallelism: zero cross-channel communication until the
#   inverse CRT), polynomial batch -> `data`/`pod` axes.
#
#     PYTHONPATH=src python -m repro.launch.dryrun_crypto --mesh both

import argparse
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import repro
from repro.kernels import ops as ops_mod
from repro.launch import analysis, hlo_analyzer
from repro.launch.mesh import make_production_mesh


def polymul_step(plan, za, zb):
    """segments (B, n, S) x2 -> product limbs (B, n, L).  The full paper
    pipeline: decompose -> per-channel no-shuffle NTT cascade -> Eq 10,
    through the ONE plan/execute entry point.  The plan defaults to the
    pure-jnp datapath: interpret-mode Pallas loops (any of the pallas*
    backends off-TPU, including pallas_fused_e2e) would bloat the
    lowered HLO on the 512-device mesh; on a real TPU pass --backend
    pallas_fused_e2e to lower the single fused kernel instead."""
    return repro.polymul(plan, za, zb)


def run(mesh_kind: str, batch: int, out_dir: str, backend: str = "jnp",
        schedule: str = "auto", row_blk: int | None = None):
    plan = repro.plan(
        n=4096, t=6, v=30, backend=backend, schedule=schedule,
        row_blk=row_blk, use_sau=False,
    )
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = 512 if mesh_kind == "multi" else 256
    seg = jax.ShapeDtypeStruct((batch, 4096, plan.config.seg_count), jnp.int64)
    ba = ("pod", "data") if mesh_kind == "multi" else ("data",)
    in_sh = NamedSharding(mesh, P(ba, None, None))
    t0 = time.time()
    rec = {"arch": "parentt_he", "shape": f"polymul_b{batch}", "mesh": mesh_kind,
           "n_devices": n_dev, "tag": "crypto", "backend": backend}
    try:
        with mesh:
            # residue-domain tensors (t, B, n): channels over `model`
            def step(za, zb):
                return polymul_step(plan, za, zb)

            jitted = jax.jit(step, in_shardings=(in_sh, in_sh))
            lowered = jitted.lower(seg, seg)
            compiled = lowered.compile()
        rec["memory"] = analysis.memory_stats(compiled)
        hlo = hlo_analyzer.analyze(compiled.as_text())
        rec["hlo"] = {"flops": hlo["flops"], "hbm_bytes": hlo["hbm_bytes"]}
        rec["collectives"] = hlo["collectives"]
        # int butterflies don't ride the MXU: report memory/collective terms
        rec["roofline"] = {
            "memory_s": hlo["hbm_bytes"] / analysis.HBM_BW,
            "collective_s": hlo["collectives"]["total"] / analysis.ICI_BW,
        }
        rec["status"] = "ok"
        print(
            f"[ok] parentt_he x b{batch} x {mesh_kind}: "
            f"hbm/dev={hlo['hbm_bytes']/1e9:.2f}GB "
            f"coll/dev={hlo['collectives']['total']/1e9:.3f}GB "
            f"memory={rec['roofline']['memory_s']*1e6:.0f}us "
            f"({time.time()-t0:.0f}s)"
        )
    except Exception as e:
        import traceback

        rec["status"] = "error"
        rec["error"] = str(e)
        rec["traceback"] = traceback.format_exc()[-3000:]
        print(f"[FAIL] parentt_he {mesh_kind}: {e}")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"dryrun_{mesh_kind}_parentt_he_b{batch}.json"), "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec


def run_dntt(mesh_kind: str, log_n: int, out_dir: str):
    """ONE long polynomial (n = 2^log_n) sharded across the `model` axis —
    the four-step NWC product with a single all-to-all per transform."""
    from repro.core import dntt

    q = 998244353  # 119 * 2^23 + 1: 2n-th roots exist up to n = 2^22
    n = 1 << log_n
    n1 = 1 << (log_n // 2)
    t = dntt.make_fourstep_tables(q, n, n1)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = 512 if mesh_kind == "multi" else 256
    spec_in = NamedSharding(mesh, P("model"))
    a_spec = jax.ShapeDtypeStruct((n,), jnp.int64)
    rec = {"arch": "parentt_dntt", "shape": f"long_2^{log_n}", "mesh": mesh_kind,
           "n_devices": n_dev, "tag": "crypto"}
    t0 = time.time()
    try:
        with mesh:
            cons = dntt.make_shard_constrain(mesh)

            def step(a, b):
                return dntt.negacyclic_mul_fourstep(a, b, t, cons)

            compiled = (
                jax.jit(step, in_shardings=(spec_in, spec_in))
                .lower(a_spec, a_spec)
                .compile()
            )
        hlo = hlo_analyzer.analyze(compiled.as_text())
        rec["hlo"] = {"flops": hlo["flops"], "hbm_bytes": hlo["hbm_bytes"]}
        rec["collectives"] = hlo["collectives"]
        rec["status"] = "ok"
        a2a = hlo["collectives"]["all-to-all"] + hlo["collectives"]["collective-permute"]
        print(
            f"[ok] parentt_dntt n=2^{log_n} x {mesh_kind}: "
            f"a2a/dev={a2a/1e6:.1f}MB coll_total/dev="
            f"{hlo['collectives']['total']/1e6:.1f}MB "
            f"hbm/dev={hlo['hbm_bytes']/1e6:.0f}MB ({time.time()-t0:.0f}s)"
        )
    except Exception as e:
        import traceback

        rec["status"] = "error"
        rec["error"] = str(e)
        rec["traceback"] = traceback.format_exc()[-3000:]
        print(f"[FAIL] parentt_dntt {mesh_kind}: {e}")
    os.makedirs(out_dir, exist_ok=True)
    with open(
        os.path.join(out_dir, f"dryrun_{mesh_kind}_parentt_dntt_2e{log_n}.json"), "w"
    ) as f:
        json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--log-n", type=int, default=20, help="dntt polynomial size")
    ap.add_argument(
        "--backend", default="jnp", choices=list(ops_mod.BACKENDS),
        help="polymul datapath; keep jnp off-TPU (interpret-mode Pallas "
             "bloats the lowered HLO)",
    )
    ap.add_argument(
        "--schedule", default="auto", choices=list(ops_mod.SCHEDULES),
        help="NTT stage schedule (auto = four_step for n >= 256)",
    )
    ap.add_argument(
        "--row-blk", type=int, default=None,
        help="kernel tile rows per grid step (None = per-kernel default)",
    )
    ap.add_argument("--out", default="dryrun_artifacts")
    args = ap.parse_args()
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    fails = 0
    for mk in meshes:
        fails += run(
            mk, args.batch, args.out, backend=args.backend,
            schedule=args.schedule, row_blk=args.row_blk,
        )["status"] != "ok"
        fails += run_dntt(mk, args.log_n, args.out)["status"] != "ok"
    raise SystemExit(1 if fails else 0)


if __name__ == "__main__":
    main()
