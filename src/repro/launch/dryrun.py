import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede every other import: jax locks the device count on first
# init.  The dry-run (and only the dry-run) builds the production mesh out
# of 512 placeholder host devices; tests/benches keep 1 device.

import argparse       # noqa: E402
import json           # noqa: E402
import time           # noqa: E402
import traceback      # noqa: E402

import jax            # noqa: E402
import jax.numpy as jnp                                    # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (ARCH_IDS, SHAPES, TrainConfig, applicable,
                           get_config, input_specs)         # noqa: E402
from repro.launch.mesh import make_production_mesh          # noqa: E402
from repro.models import Model                              # noqa: E402
from repro.sharding import rules as rules_lib               # noqa: E402
from repro.train import step as step_lib                    # noqa: E402
from repro.utils import hlo as hlo_lib                      # noqa: E402
from repro.utils import hlo2 as hlo2_lib                    # noqa: E402

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__),
                            "..", "..", "..", "artifacts", "dryrun")


def dryrun_config(arch: str, constrain: bool = False):
    """bf16 compute for the roofline target (197 TF/s bf16 peak)."""
    cfg = get_config(arch).replace(dtype="bfloat16", param_dtype="bfloat16")
    if constrain:
        cfg = cfg.replace(constrain_acts=True)
    return cfg


def tcfg_for(cfg) -> TrainConfig:
    n = Model(cfg).n_params()
    opt = "adafactor" if n > 100e9 else "adamw"
    micro = 8 if n > 100e9 else (4 if n > 8e9 else 0)
    remat = cfg.remat if cfg.remat != "none" else \
        ("dots" if n > 2e9 else "none")
    return TrainConfig(optimizer=opt, microbatch=micro), remat


def _front_kw(cfg, specs):
    kw = {}
    if "enc_embeds" in specs:
        kw["enc_embeds"] = specs["enc_embeds"]
    if "prefix_embeds" in specs:
        kw["prefix_embeds"] = specs["prefix_embeds"]
    return kw


def lower_cell(arch: str, shape_name: str, mesh, constrain: bool = False,
               gather_once: bool = False, remat_override: str = "",
               micro_override: int = -1):
    cfg = dryrun_config(arch, constrain)
    if remat_override:
        cfg = cfg.replace(remat=remat_override)
    if constrain or gather_once:
        jax.sharding.set_mesh(mesh)
    shape = SHAPES[shape_name]
    ok, reason = applicable(cfg, shape)
    if not ok:
        return {"status": "skipped", "reason": reason}
    model = Model(cfg)
    specs = input_specs(cfg, shape)
    bsh = rules_lib.batch_shardings_for(specs, mesh)

    if shape.kind == "train":
        tcfg, remat = tcfg_for(cfg)
        if remat_override:
            remat = remat_override
        import dataclasses as _dc
        if gather_once:
            tcfg = _dc.replace(tcfg, gather_once=True)
        if micro_override >= 0:
            tcfg = _dc.replace(tcfg, microbatch=micro_override)
        if remat != cfg.remat:
            cfg = cfg.replace(remat=remat)
            model = Model(cfg)
        state_abs = step_lib.abstract_state(model, tcfg)
        state_sh = step_lib.state_shardings(model, tcfg, mesh)
        fn = step_lib.build_train_step(model, tcfg)
        jitted = jax.jit(fn, in_shardings=(state_sh, bsh),
                         out_shardings=(state_sh, None))
        lowered = jitted.lower(state_abs, specs)
    else:
        params_abs = model.abstract()
        params_sh = rules_lib.param_shardings(model.spec, mesh)
        cache_len = shape.seq_len
        b = shape.global_batch
        cache_abs = jax.eval_shape(
            lambda: model.init_cache(b, cache_len))
        cache_sh = rules_lib.cache_shardings(cache_abs, mesh)
        if shape.kind == "prefill":
            def fn(params, cache, batch):
                kw = _front_kw(cfg, batch)
                logits, cache, _ = model.apply(
                    params, batch["tokens"], mode="prefill", cache=cache,
                    **kw)
                return logits[:, -1], cache
        else:
            def fn(params, cache, batch):
                logits, cache, _ = model.apply(
                    params, batch["tokens"], mode="decode", cache=cache,
                    pos=batch["pos"])
                return logits[:, 0], cache
        jitted = jax.jit(fn, in_shardings=(params_sh, cache_sh, bsh),
                         out_shardings=(None, cache_sh))
        lowered = jitted.lower(params_abs, cache_abs, specs)

    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    text = compiled.as_text()
    coll = hlo_lib.collective_bytes(text)            # body-once (raw)
    coll_scaled = hlo2_lib.collective_bytes_scaled(text)  # x trip counts
    n_devices = mesh.devices.size
    result = {
        "status": "ok",
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "n_devices": int(n_devices),
        "n_params": Model(cfg).n_params(),
        "compile_sec": round(compile_s, 1),
        "flops_per_device": float(cost.get("flops", -1)),
        "bytes_accessed_per_device": float(cost.get("bytes accessed", -1)),
        "collectives": {k: float(v) for k, v in coll.items()},
        "collectives_scaled": {k: float(v) for k, v in coll_scaled.items()},
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", -1),
            "output_bytes": getattr(mem, "output_size_in_bytes", -1),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", -1),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", -1),
        },
        "hlo_ops": {
            k: hlo_lib.count_ops(text, k)
            for k in ("all-reduce", "all-gather", "reduce-scatter",
                      "all-to-all", "collective-permute", "while", "fusion")
        },
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    help="shape name or 'all'")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--constrain", action="store_true",
                    help="activation sharding constraints (PERF variant)")
    ap.add_argument("--gather-once", action="store_true",
                    help="hoist FSDP param all-gather out of microbatching")
    ap.add_argument("--tp", type=int, default=0,
                    help="override model-axis size (mesh 256/tp x tp)")
    ap.add_argument("--remat", default="",
                    help="override remat policy (none|dots|full)")
    ap.add_argument("--microbatch", type=int, default=-1,
                    help="override microbatch count")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    os.makedirs(args.out, exist_ok=True)

    for multi_pod in meshes:
        if args.tp:
            mesh = jax.make_mesh((256 // args.tp, args.tp),
                                 ("data", "model"))
            mesh_name = f"{256 // args.tp}x{args.tp}"
        else:
            mesh = make_production_mesh(multi_pod=multi_pod)
            mesh_name = "2x16x16" if multi_pod else "16x16"
        for arch in archs:
            for shape_name in shapes:
                suffix = ""
                if args.constrain:
                    suffix += "__opt"
                if args.gather_once:
                    suffix += "__g1"
                if args.remat:
                    suffix += f"__r{args.remat}"
                if args.microbatch >= 0:
                    suffix += f"__m{args.microbatch}"
                tag = f"{arch}__{shape_name}__{mesh_name}" + suffix
                path = os.path.join(args.out, tag + ".json")
                t0 = time.time()
                try:
                    res = lower_cell(arch, shape_name, mesh,
                                     constrain=args.constrain,
                                     gather_once=args.gather_once,
                                     remat_override=args.remat,
                                     micro_override=args.microbatch)
                except Exception as e:            # noqa: BLE001
                    res = {"status": "error", "error": repr(e),
                           "traceback": traceback.format_exc()}
                res["wall_sec"] = round(time.time() - t0, 1)
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                status = res["status"]
                extra = ""
                if status == "ok":
                    mem = res["memory"]
                    extra = (f" flops/dev={res['flops_per_device']:.3e}"
                             f" coll={res['collectives_scaled']['wire_bytes']:.3e}B"
                             f" mem[args={mem['argument_bytes']:.2e}"
                             f" temp={mem['temp_bytes']:.2e}"
                             f" out={mem['output_bytes']:.2e}]B"
                             f" compile={res['compile_sec']}s")
                elif status == "error":
                    extra = " " + res["error"][:120]
                print(f"[dryrun] {tag}: {status}{extra}", flush=True)


if __name__ == "__main__":
    main()
