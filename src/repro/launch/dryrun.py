import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. constructs abstract (ShapeDtypeStruct) state + inputs with the cell's
     shardings — no device allocation anywhere,
  3. ``jit(step).lower(...).compile()`` — a sharding mismatch, a collective
     the partitioner can't build, or an OOM-at-compile is a FAILURE,
  4. records memory_analysis() (bytes/device), cost_analysis() (flops,
     bytes), and the parsed collective schedule into
     artifacts/dryrun/<arch>__<shape>__<mesh>.json for §Roofline.

Run:  PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
          [--mesh single|multi|both] [--grad-compress none|bf16|int8]
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import base as configs
from repro.distributed import sharding
from repro.launch import mesh as mesh_lib
from repro.launch import train as train_lib
from repro.models import transformer
from repro.roofline import analysis as roofline
from repro.roofline import extrapolate, memory_model
from repro.serving import engine as serving_engine
from repro.serving import kvcache

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         "artifacts", "dryrun")


def _mesh_desc(mesh) -> str:
    return "x".join(str(s) for s in mesh.devices.shape)


def _default_group(mesh) -> int:
    return int(np.prod(mesh.devices.shape))


def lower_train_cell(cfg, shape, mesh, *, grad_compress="none",
                     rules=None, extra_jit_kwargs=None):
    step_cfg = train_lib.StepConfig(grad_compress=grad_compress)
    ef = grad_compress == "int8" and "pod" in mesh.axis_names
    n_pods = dict(zip(mesh.axis_names, mesh.devices.shape)).get("pod", 0)
    st = train_lib.state_spec(cfg, ef_pods=n_pods if ef else 0)
    st_sh = train_lib.state_shardings(cfg, mesh, rules, ef_residual=ef)
    batch = train_lib.batch_abstract(cfg, shape)
    b_sh = train_lib.batch_shardings(cfg, shape, mesh, rules)
    fn = train_lib.make_train_step(cfg, mesh, step_cfg, rules=rules,
                                   shape=shape)
    with jax.set_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=(st_sh, b_sh),
                         out_shardings=(st_sh, NamedSharding(mesh, P())),
                         donate_argnums=(0,))
        lowered = jitted.lower(st, batch)
        compiled = lowered.compile()
    return lowered, compiled


def lower_decode_cell(cfg, shape, mesh, *, rules=None):
    b, s = shape.global_batch, shape.seq_len
    params_abs = train_lib.state_spec(cfg)["params"]
    p_sh = train_lib.state_shardings(cfg, mesh, rules)["params"]
    cache_abs = kvcache.cache_spec(cfg, b, s)
    cache_specs = kvcache.cache_partition_spec(cfg, b, s, mesh)
    cache_sh = jax.tree.map(lambda sp: NamedSharding(mesh, sp), cache_specs)
    dp = sharding.batch_spec(mesh, b)
    tok = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    lens = jax.ShapeDtypeStruct((b,), jnp.int32)
    tok_sh = NamedSharding(mesh, dp)
    lens_sh = NamedSharding(mesh, dp)
    fn = serving_engine.make_decode(cfg)
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            fn, in_shardings=(p_sh, cache_sh, tok_sh, lens_sh),
            out_shardings=(NamedSharding(mesh, dp), cache_sh, lens_sh),
            donate_argnums=(1,))
        lowered = jitted.lower(params_abs, cache_abs, tok, lens)
        compiled = lowered.compile()
    return lowered, compiled


def lower_prefill_cell(cfg, shape, mesh, *, rules=None):
    b, s = shape.global_batch, shape.seq_len
    params_abs = train_lib.state_spec(cfg)["params"]
    p_sh = train_lib.state_shardings(cfg, mesh, rules)["params"]
    cache_specs = kvcache.cache_partition_spec(cfg, b, s, mesh)
    cache_sh = jax.tree.map(lambda sp: NamedSharding(mesh, sp), cache_specs)
    dp = sharding.batch_spec(mesh, b)
    tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
    fn = serving_engine.make_prefill(cfg, max_len=s, last_only=True)
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            fn, in_shardings=(p_sh, NamedSharding(mesh, dp)),
            out_shardings=(NamedSharding(mesh, dp), cache_sh,
                           NamedSharding(mesh, dp)))
        lowered = jitted.lower(params_abs, tok)
        compiled = lowered.compile()
    return lowered, compiled


def _lower_one(cfg, shape, mesh, *, grad_compress, rules):
    if shape.kind == "train":
        return lower_train_cell(cfg, shape, mesh,
                                grad_compress=grad_compress, rules=rules)
    if shape.kind == "decode":
        return lower_decode_cell(cfg, shape, mesh, rules=rules)
    return lower_prefill_cell(cfg, shape, mesh, rules=rules)


def _cost_triple(compiled, default_group):
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    wire = sum(o.wire_bytes
               for o in roofline.parse_collectives(hlo, default_group))
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)), wire)


def extrapolated_cost(cfg, shape, mesh, *, grad_compress, rules,
                      default_group):
    """Depth-variant unrolled lowering -> exact (flops, bytes, wire).

    All internal scans (attention kv loop, ssm/mlstm chunks, MoE dispatch
    groups — 32 groups at token_chunk=32768) unroll in the variants, so
    every iteration is counted at the deployed configuration.
    """
    variants, full = extrapolate.depth_variants(cfg)
    samples = []
    for vcfg, counts in variants:
        _, c = _lower_one(vcfg, shape, mesh, grad_compress=grad_compress,
                          rules=rules)
        triple = _cost_triple(c, default_group)
        samples.append((counts, triple))
    out = []
    for i in range(3):
        out.append(extrapolate.solve_and_extrapolate(
            [(c, v[i]) for c, v in samples], full))
    out[0] += extrapolate.slstm_recurrent_flops(
        cfg, shape, train=(shape.kind == "train"))
    return tuple(out)


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             grad_compress: str = "none", rules=None, save: bool = True,
             tag: str = "", exact_cost: bool = True,
             cfg_overrides: dict | None = None) -> dict:
    import dataclasses as dc
    cfg = configs.get(arch)
    if cfg_overrides:
        moe_kw = {k[4:]: v for k, v in cfg_overrides.items()
                  if k.startswith("moe_")}
        top = {k: v for k, v in cfg_overrides.items()
               if not k.startswith("moe_")}
        if moe_kw and cfg.moe is not None:
            top["moe"] = dc.replace(cfg.moe, **moe_kw)
        cfg = dc.replace(cfg, **top)
    shape = configs.SHAPES[shape_name]
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    lowered, compiled = _lower_one(cfg, shape, mesh,
                                   grad_compress=grad_compress, rules=rules)
    compile_s = time.time() - t0

    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    mem_stats = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes",
                                        None),
        "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
    }
    hlo = compiled.as_text()
    chips = int(np.prod(mesh.devices.shape))
    raw_cost = dict(cost)
    wire_override = None
    if exact_cost:
        t1 = time.time()
        fx, bx, wx = extrapolated_cost(
            cfg, shape, mesh, grad_compress=grad_compress, rules=rules,
            default_group=_default_group(mesh))
        cost = {"flops": fx, "bytes accessed": bx}
        wire_override = wx
        extrap_s = time.time() - t1
    cache_b = (kvcache.cache_bytes(cfg, shape.global_batch, shape.seq_len)
               if shape.kind in ("decode", "prefill") else 0)
    mem_model = memory_model.analytic_memory_bytes(cfg, shape, mesh,
                                                   cache_bytes=cache_b)
    report = roofline.analyze(
        arch=arch, shape=shape_name, mesh_desc=_mesh_desc(mesh), chips=chips,
        device_kind=roofline.V5E, cost=cost, hlo_text=hlo,
        model_flops_global=roofline.model_flops(cfg, shape),
        memory_stats=mem_stats, default_group=_default_group(mesh),
        wire_bytes_override=wire_override,
        model_bytes_per_device=mem_model)
    out = json.loads(report.to_json())
    out["compile_s"] = compile_s
    out["grad_compress"] = grad_compress
    out["tag"] = tag
    if exact_cost:
        out["raw_scanned_cost"] = {
            "flops": raw_cost.get("flops"),
            "bytes_accessed": raw_cost.get("bytes accessed")}
        out["extrapolate_s"] = extrap_s
    if save:
        os.makedirs(ARTIFACTS, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        fn = os.path.join(
            ARTIFACTS,
            f"{arch}__{shape_name}__{_mesh_desc(mesh)}{suffix}.json")
        with open(fn, "w") as f:
            json.dump(out, f, indent=1)
    return out


def cells_for(arch: str) -> list[str]:
    return [s.name for s in configs.cells(arch)]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--grad-compress", default="none",
                    choices=["none", "bf16", "int8"])
    ap.add_argument("--rules", default="default",
                    choices=list(sharding.RULE_SETS))
    ap.add_argument("--moe-grouped", action="store_true",
                    help="grouped DP-local MoE dispatch (hillclimb)")
    ap.add_argument("--n-groups", type=int, default=16)
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    rules = sharding.RULE_SETS[args.rules]
    overrides = ({"moe_grouped_dispatch": True, "moe_n_groups": args.n_groups}
                 if args.moe_grouped else None)

    archs = [args.arch] if args.arch else list(configs.ARCH_IDS)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = []
    for arch in archs:
        shapes = [args.shape] if args.shape else cells_for(arch)
        for shape_name in shapes:
            for multi in meshes:
                desc = f"{arch} x {shape_name} x {'2x16x16' if multi else '16x16'}"
                try:
                    out = run_cell(arch, shape_name, multi,
                                   grad_compress=args.grad_compress,
                                   rules=rules, tag=args.tag,
                                   cfg_overrides=overrides)
                    print(f"OK   {desc}: step={out['step_s']*1e3:.2f}ms "
                          f"bottleneck={out['bottleneck']} "
                          f"frac={out['roofline_fraction']:.3f} "
                          f"compile={out['compile_s']:.0f}s")
                except Exception as e:  # noqa: BLE001
                    failures.append((desc, e))
                    print(f"FAIL {desc}: {type(e).__name__}: {e}")
                    traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run cells failed")
    print("all dry-run cells passed")


if __name__ == "__main__":
    main()
