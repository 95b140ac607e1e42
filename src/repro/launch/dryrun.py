"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

MUST set the fake-device count before any other import touches jax — the
device count is locked at first backend init.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

# ruff: noqa: E402
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, shapes_for
from repro.configs.base import ModelConfig, ShapeSpec, SHAPES
from repro.core.topology import chip_spec
from repro.distributed.sharding import MeshContext, build_shardings, mesh_context
from repro.launch import roofline as RL
from repro.launch.mesh import make_context
from repro.models import registry as R
from repro.train import AdamWConfig
from repro.train.step import TrainState, make_train_step, state_shardings

# Per-arch microbatch counts for train_4k: chosen so one microbatch of
# activations (seq 4096, remat=block) fits 16 GB HBM next to params+opt.
MICROBATCHES = {
    "deepseek-67b": 16,
    "qwen1.5-32b": 16,
    "zamba2-7b": 8,
    "minicpm-2b": 4,
    "qwen2.5-3b": 4,
    "deepseek-v2-lite-16b": 4,
    "olmoe-1b-7b": 4,
    "mamba2-1.3b": 4,
    "whisper-medium": 4,
    "qwen2-vl-2b": 4,
}


def dryrun_config(
    arch: str, shape: ShapeSpec, overrides: dict | None = None, multi_pod: bool = False
) -> ModelConfig:
    """The execution policy used on the production mesh (not the smoke one)."""
    cfg = get_config(arch)
    over: dict = dict(dtype="bfloat16", remat="block", scan_layers=True)
    if shape.kind == "train":
        # each microbatch must still cover every data-parallel lane
        lanes = 32 if multi_pod else 16
        over["num_microbatches"] = min(
            MICROBATCHES.get(arch, 4), shape.global_batch // lanes
        )
    if cfg.num_experts:
        # EP exchange for bulk shapes; replicate-and-reduce at decode
        over["moe_impl"] = "ep_shardmap" if shape.kind != "decode" else "gspmd"
    if overrides:
        over.update(overrides)
    return cfg.scaled(**over)


def build_cell(api: R.ModelApi, shape: ShapeSpec, ctx):
    """(fn, example_args, in_shardings) for one (arch × shape) cell."""
    cfg = api.cfg
    batch_sds, batch_axes = R.input_specs(cfg, shape)
    batch_sh = build_shardings(batch_axes, batch_sds, ctx)

    if shape.kind == "train":
        step = make_train_step(api, AdamWConfig(schedule=cfg.lr_schedule))
        state_sds = jax.eval_shape(lambda k: TrainState.create(api, k), jax.random.PRNGKey(0))
        state_sh = state_shardings(api, ctx)
        return step, (state_sds, batch_sds), (state_sh, batch_sh)

    param_sds, param_axes = R.param_shape_specs(cfg)
    param_sh = build_shardings(param_axes, param_sds, ctx)

    if shape.kind == "prefill":
        return api.prefill, (param_sds, batch_sds), (param_sh, batch_sh)

    # decode
    cache_sds, cache_axes = R.cache_shape_specs(cfg, shape)
    cache_sh = build_shardings(cache_axes, cache_sds, ctx)
    tok_sds = batch_sds["tokens"]
    tok_sh = batch_sh["tokens"]
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
    pos_sh = NamedSharding(ctx.mesh, P())
    fn = lambda params, tokens, cache, pos: api.decode_step(params, tokens, cache, pos)
    return (
        fn,
        (param_sds, tok_sds, cache_sds, pos_sds),
        (param_sh, tok_sh, cache_sh, pos_sh),
    )


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    device_kind: str,
    out_dir: str | None = None,
    overrides: dict | None = None,
    verbose: bool = True,
) -> dict:
    """Compile one cell and price its roofline for ``device_kind`` (the
    target chip's ``jax.Device.device_kind``; the fake devices the cell
    compiles on are CPUs)."""
    shape = SHAPES[shape_name]
    overrides = dict(overrides or {})
    tag = overrides.pop("tag", "")
    cfg = dryrun_config(arch, shape, overrides, multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = 512 if multi_pod else 256

    if shape.name == "long_500k" and not cfg.supports_long_context:
        art = {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "status": "skipped",
            "reason": "pure full-attention arch; sub-quadratic required (DESIGN.md)",
        }
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            with open(os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json"), "w") as f:
                json.dump(art, f, indent=1)
        if verbose:
            print(f"[{arch} × {shape_name} × {mesh_name}] SKIPPED: {art['reason']}")
        return art

    exchange_axis = "data" if cfg.exchange_over_data else "model"
    # dryrun models the paper's fixed 256-chip pod on 512 fake devices in
    # one process, so the mesh is pinned here — (2,16,16) / (16,16), the
    # shapes the artifact labels above promise — rather than derived from
    # the host topology (a real multi-host launch uses jax.process_count()
    # via make_production_mesh instead).
    from repro.compat import make_mesh as _compat_make_mesh

    mesh = _compat_make_mesh(
        (2, 16, 16) if multi_pod else (16, 16),
        ("pod", "data", "model") if multi_pod else ("data", "model"),
    )
    ctx = make_context(mesh=mesh, exchange_impl=cfg.exchange_impl)
    rules = ctx.rules
    if cfg.exchange_over_data:
        # the paper's topology: shuffle between coarse (data) units, keep
        # fine-grained TP on the fast model axis inside each unit
        rules = rules.replace(experts="data", expert_fsdp="model")
    if cfg.uneven_shards:
        rules = rules.replace(allow_uneven=True)
    if cfg.sequence_parallel:
        rules = rules.replace(seq_sp="model")
    if cfg.dp_only:
        # ZeRO-3: every chip is a data lane.  Only the batch mapping changes;
        # per-spec mesh-axis de-duplication (sharding.logical_sharding) drops
        # the heads/d_ff constraints from activations automatically while
        # parameter specs keep their 256-way (fsdp x model) storage sharding.
        batch = ("pod", "data", "model") if multi_pod else ("data", "model")
        rules = rules.replace(batch=batch)
    if rules is not ctx.rules or exchange_axis != ctx.exchange_axis:
        ctx = MeshContext(
            mesh=ctx.mesh, rules=rules,
            exchange_axis=exchange_axis, data_axes=ctx.data_axes,
            pod_axis=ctx.pod_axis, exchange_impl=ctx.exchange_impl,
        )
    api = R.build(cfg)
    art: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "chips": chips,
                 "device_kind": device_kind, "overrides": overrides, "tag": tag}
    with mesh_context(ctx):
        fn, args, in_sh = build_cell(api, shape, ctx)
        t0 = time.perf_counter()
        lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()

        try:
            mem = compiled.memory_analysis()
            art["memory_analysis"] = {
                k: int(getattr(mem, k))
                for k in (
                    "argument_size_in_bytes",
                    "output_size_in_bytes",
                    "temp_size_in_bytes",
                    "generated_code_size_in_bytes",
                )
                if hasattr(mem, k)
            }
        except Exception as e:  # CPU backend may not implement it
            art["memory_analysis"] = {"error": str(e)}

        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        art["xla_cost_analysis"] = {
            k: float(v) for k, v in cost.items()
            if isinstance(v, (int, float)) and k in ("flops", "bytes accessed", "transcendentals")
        }
        hlo = compiled.as_text()
        # trip-count-corrected per-device cost (XLA's counts while bodies once)
        from repro.launch import hlo_cost

        corrected = hlo_cost.analyze(hlo)
        art["cost_analysis"] = {
            "flops": corrected["flops"],
            "bytes accessed": corrected["bytes"],
        }
        art["unknown_trip_whiles"] = corrected["unknown_trip_whiles"]
        art["collective_bytes"] = corrected["collective_bytes"]
        art["async_collective_bytes"] = corrected["async_collective_bytes"]
        art["hlo_bytes"] = len(hlo)
        art["lower_s"] = t1 - t0
        art["compile_s"] = t2 - t1

    n_active = R.param_count(cfg, active_only=True)
    n_total = R.param_count(cfg)
    art["params"] = n_total
    art["active_params"] = n_active
    art["model_flops"] = RL.model_flops(cfg, shape, n_active)
    art["ideal_bytes"] = RL.ideal_memory_bytes(
        cfg, shape, n_active, n_total, cfg.num_microbatches
    )
    art["status"] = "ok"

    terms = RL.from_artifact(art)
    art["roofline"] = terms.row()
    if verbose:
        print(
            f"[{arch} × {shape_name} × {mesh_name}] compile={art['compile_s']:.1f}s "
            f"flops/chip={art['cost_analysis'].get('flops', 0):.3g} "
            f"dominant={terms.dominant} roofline={100*terms.roofline_fraction:.1f}%"
        )
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fn_out = f"{arch}__{shape_name}__{mesh_name}{('__' + tag) if tag else ''}.json"
        with open(os.path.join(out_dir, fn_out), "w") as f:
            json.dump(art, f, indent=1, default=str)
    return art


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="all", help="arch id or 'all'")
    p.add_argument("--shape", default="all", help="shape name or 'all'")
    p.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    p.add_argument("--out", default="artifacts/dryrun")
    p.add_argument("--device-kind", required=True,
                   help="target chip the rooflines are priced for, as JAX "
                        "reports it (e.g. 'TPU v5 lite')")
    p.add_argument("--set", action="append", default=[],
                   help="cfg override key=value (e.g. exchange_impl=xla)")
    args = p.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    chip_spec(args.device_kind)  # an unknown kind fails before compiling
    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        cfg = get_config(arch)
        shapes = [s.name for s in shapes_for(cfg)] + (
            ["long_500k"] if not cfg.supports_long_context else []
        )
        if args.shape != "all":
            shapes = [args.shape]
        for shape_name in shapes:
            for mp in meshes:
                try:
                    run_cell(arch, shape_name, mp, args.device_kind, args.out,
                             overrides or None)
                except Exception:
                    failures.append((arch, shape_name, mp))
                    print(f"FAILED: {arch} × {shape_name} × multi_pod={mp}")
                    traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run cells failed: {failures}")
    print("all requested dry-run cells passed")


if __name__ == "__main__":
    main()
