import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT lower + compile every (arch x input-shape) on the
production mesh, with ShapeDtypeStruct inputs (no allocation).

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape decode_32k --multi-pod

Outputs memory_analysis / cost_analysis and writes a JSON record (plus the
compiled HLO text for the roofline collective parser) under experiments/dryrun/.
"""

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import get_config
from repro.dist import api as A
from repro.dist import sharding as SH
from repro.launch.mesh import make_production_mesh
from repro.models.model import INPUT_SHAPES, input_specs
from repro.optim.adamw import adamw_init

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# Archs where splitting a <100M model over 256 chips is counterproductive
# (DESIGN.md §5): baseline mode is fsdp.
FSDP_BASELINE = {"whisper-base"}

# long_500k policy (DESIGN.md §5): whisper skipped; full-attention archs run
# the documented sliding-window serving variant.
LONG_SKIP = {"whisper-base"}
SWA_WINDOW = 8192
SUBQUADRATIC = {"xlstm-125m"}          # no attention KV at all


def default_mode(arch: str) -> str:
    return "fsdp" if arch in FSDP_BASELINE else "pipeline"


def window_for(cfg, shape_name: str):
    if shape_name != "long_500k":
        return None
    if cfg.family in ("ssm",):
        return None
    return SWA_WINDOW


def opt_dtype_for(cfg) -> str:
    # fp32 (m,v) for a 398B model does not fit 256 chips (DESIGN.md §8)
    return "bfloat16" if cfg.param_count() > 100e9 else "float32"


def shardings(mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def run_dryrun(arch: str, shape_name: str, *, mode: str = None,
               multi_pod: bool = False, save: bool = True,
               n_micro: int = None, verbose: bool = True,
               variant: str = "", runner_kw: dict = None):
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mode = mode or default_mode(arch)
    if shape_name == "long_500k" and arch in LONG_SKIP:
        raise SystemExit(f"{arch} x long_500k skipped (DESIGN.md §5)")

    mesh = make_production_mesh(multi_pod=multi_pod)
    kw = dict(runner_kw or {})
    if mode == "pipeline" and cfg.moe is not None \
            and cfg.moe.n_experts % 16 == 0 and "expert_parallel" not in kw:
        kw["expert_parallel"] = True  # production default: EP is numerically
        # identical to dense dispatch and 5.9x lighter on collectives (§Perf)
    runner = A.build_runner(cfg, mode, mesh, n_microbatches=n_micro, **kw)
    rcfg = runner.cfg  # semantic runner swaps in the branch config

    key = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(runner.init, key)
    p_specs = runner.param_specs(params_shape)
    p_shard = shardings(mesh, p_specs)
    batch = input_specs(rcfg, shape)
    b_specs = A.batch_specs(rcfg, mesh, batch)
    b_shard = shardings(mesh, b_specs)
    wo = window_for(cfg, shape_name)

    t0 = time.time()
    if shape.kind == "train":
        opt_shape = jax.eval_shape(
            lambda p: adamw_init(p, opt_dtype_for(cfg)), params_shape)
        o_specs = A.make_opt_specs(p_specs)
        if multi_pod and cfg.param_count() > 100e9:
            o_specs = A.pod_shard_opt_specs(o_specs, params_shape, mesh)
        o_shard = shardings(mesh, o_specs)
        step = A.make_train_step(runner)
        jf = jax.jit(step,
                     in_shardings=(p_shard, o_shard, b_shard),
                     out_shardings=(p_shard, o_shard, None))
        lowered = jf.lower(params_shape, opt_shape, batch)
    elif shape.kind == "prefill":
        jf = jax.jit(runner.prefill_step,
                     in_shardings=(p_shard, b_shard),
                     out_shardings=None)
        lowered = jf.lower(params_shape, batch)
    else:  # decode
        cache_len = shape.seq_len
        cache_shape = jax.eval_shape(
            lambda: runner.init_cache(shape.global_batch, cache_len, wo))
        c_specs = runner.cache_specs(cache_shape)
        c_shard = shardings(mesh, c_specs)
        step = A.make_serve_step(runner, window_override=wo)
        jf = jax.jit(step,
                     in_shardings=(p_shard, c_shard, b_shard, None),
                     out_shardings=(None, c_shard))
        idx = jax.ShapeDtypeStruct((), jnp.int32)
        lowered = jf.lower(params_shape, cache_shape, batch, idx)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    n_dev = mesh.devices.size
    record = {
        "arch": arch, "shape": shape_name, "mode": mode, "variant": variant,
        "multi_pod": multi_pod, "n_devices": int(n_dev),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops": float(cost.get("flops", -1)),
        "bytes_accessed": float(cost.get("bytes accessed", -1)),
        "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "peak_bytes": int(getattr(mem, "peak_memory_in_bytes", 0) or 0),
        "generated_code_bytes": int(
            getattr(mem, "generated_code_size_in_bytes", 0)),
        "param_count": int(cfg.param_count()),
        "active_param_count": int(cfg.active_param_count()),
    }
    if verbose:
        print(json.dumps(record, indent=2))
        print("memory_analysis:", mem)

    if save:
        OUT_DIR.mkdir(parents=True, exist_ok=True)
        tag = f"{arch}__{shape_name}__{'pod2' if multi_pod else 'pod1'}__{mode}"
        if variant:
            tag += f"__{variant}"
        (OUT_DIR / f"{tag}.json").write_text(json.dumps(record, indent=2))
        (OUT_DIR / f"{tag}.hlo.txt").write_text(compiled.as_text())
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(INPUT_SHAPES))
    ap.add_argument("--mode", default=None,
                    choices=[None, "fsdp", "semantic", "pipeline"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--n-micro", type=int, default=None)
    ap.add_argument("--no-save", action="store_true")
    ap.add_argument("--variant", default="")
    ap.add_argument("--no-zero-data", action="store_true")
    ap.add_argument("--ep", action="store_true")
    ap.add_argument("--flash-decode", action="store_true",
                    help="shard attention KV cache length over 'data'")
    args = ap.parse_args()
    kw = {}
    if args.no_zero_data:
        kw["zero_data"] = False
    if args.ep:
        kw["expert_parallel"] = True
    if args.flash_decode:
        kw["shard_cache_len"] = True
    run_dryrun(args.arch, args.shape, mode=args.mode,
               multi_pod=args.multi_pod, save=not args.no_save,
               n_micro=args.n_micro, variant=args.variant, runner_kw=kw)


if __name__ == "__main__":
    main()
