"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.


For each cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. builds abstract (ShapeDtypeStruct) model state + inputs — no HBM,
  3. lowers + compiles the cell's entry point (train_step / prefill_step /
     serve_step) under the arch's sharding plan,
  4. records memory_analysis(), cost_analysis(), and the collective-op
     byte census parsed from the optimized HLO,
  5. writes a JSON artifact to ``artifacts/dryrun/`` for §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""
import argparse
import json
import os
import re
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.sharding.plan import Plan, param_shardings, use_plan
from repro.train.optimizer import get_optimizer
from repro.train.step import abstract_train_state, make_train_step

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__),
                            "..", "..", "..", "artifacts", "dryrun")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
                "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([\d,]*)\][^ ]*\s+"
    r"(all-reduce-start|all-gather-start|reduce-scatter|all-to-all|"
    r"collective-permute-start|all-reduce|all-gather|collective-permute)"
    r"\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_census(hlo_text: str) -> dict:
    """Per-device wire-byte census of collective ops in optimized HLO.

    Ring-algorithm wire factors (bytes actually crossing links, per device):
      all-reduce       2(n-1)/n x payload     (reduce-scatter + all-gather)
      all-gather       (n-1)/n x result       (result = gathered size)
      reduce-scatter   (n-1)   x result       (input = n x result)
      all-to-all       (n-1)/n x payload
      collective-permute  1 x payload
    """
    ops = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        dtype, dims, op = m.groups()
        op = op.replace("-start", "")
        payload = _shape_bytes(dtype, dims)
        g = _GROUPS_RE.search(line)
        if g:
            n = int(g.group(2))
        else:
            gl = _GROUPS_LIST_RE.search(line)
            n = len(gl.group(1).split(",")) if gl else 2
        if op == "all-reduce":
            wire = 2 * (n - 1) / max(n, 1) * payload
        elif op == "all-gather":
            wire = (n - 1) / max(n, 1) * payload
        elif op == "reduce-scatter":
            wire = (n - 1) * payload
        elif op == "all-to-all":
            wire = (n - 1) / max(n, 1) * payload
        else:                                   # collective-permute
            wire = payload
        ops.append({"op": op, "payload_bytes": payload, "group": n,
                    "wire_bytes": wire})
    total = sum(o["wire_bytes"] for o in ops)
    by_op: dict = {}
    for o in ops:
        by_op.setdefault(o["op"], [0, 0.0])
        by_op[o["op"]][0] += 1
        by_op[o["op"]][1] += o["wire_bytes"]
    return {"n_collectives": len(ops), "wire_bytes_per_device": total,
            "by_op": {k: {"count": c, "wire_bytes": b}
                      for k, (c, b) in by_op.items()},
            "largest": sorted(ops, key=lambda o: -o["wire_bytes"])[:8]}


# ---------------------------------------------------------------------------
# Cell construction
# ---------------------------------------------------------------------------


FSDP_ONLY_RULES = {
    "heads": None, "kv_heads": None, "ff": None, "vocab": None,
    "experts": "model",                 # MoE keeps expert parallelism
    "seq": None, "kv_seq": ("data", "model"),
    "batch": ("pod", "data", "model"),
    "fsdp": ("data", "model"),
}

# MoE variant: batch must NOT span "model" (the dispatch needs tokens on
# "data" x experts on "model" to lower to all-to-all; sharing the axis
# replicates the experts — measured 185 GiB/dev, §Perf H4)
FSDP_EP_RULES = {
    "heads": None, "kv_heads": None, "ff": None, "vocab": None,
    "experts": "model",
    "seq": "model",                     # SP still pays for itself here
    "kv_seq": "model",
    "batch": ("pod", "data"),
    "fsdp": "data",
}


def build_plan(cfg: ModelConfig, shape_name: str, mesh) -> Plan:
    rules = dict(configs.plan_rule_overrides(cfg, shape_name))
    if cfg.sharding_profile in ("fsdp_only", "fsdp_ep"):
        base = dict(FSDP_ONLY_RULES if cfg.sharding_profile == "fsdp_only"
                    else FSDP_EP_RULES)
        if configs.SHAPES[shape_name].global_batch == 1:
            base["batch"] = None
        rules = {**base, **{k: v for k, v in rules.items()
                            if k not in ("seq", "batch")}}
        if configs.SHAPES[shape_name].global_batch == 1:
            rules["batch"] = None
        cfg_fsdp = True
    else:
        cfg_fsdp = cfg.fsdp
    return Plan(mesh=mesh, fsdp=cfg_fsdp, rules=rules)


def _batch_shardings(plan: Plan, batch_specs: dict):
    def leaf(sds):
        if sds.ndim == 1:
            return plan.sharding("batch")
        if sds.ndim == 2:
            return plan.sharding("batch", "seq")
        return plan.sharding("batch", "seq", None)
    return jax.tree.map(leaf, batch_specs)


def _cache_shardings(plan: Plan, cache_specs: dict):
    def with_key(path, sds):
        key = str(getattr(path[-1], "key", ""))
        if key == "pos":
            return plan.sharding("batch")
        if key in ("k", "v"):
            return plan.sharding(None, "batch", "kv_seq", None, None)
        if key in ("k_scale", "v_scale"):
            return plan.sharding(None, "batch", "kv_seq", None)
        if key == "ssm_h":
            return plan.sharding(None, "batch", "heads", None, None)
        if key == "conv_tail":
            return plan.sharding(None, "batch", None, None)
        return plan.sharding(*([None] * sds.ndim))
    return jax.tree_util.tree_map_with_path(with_key, cache_specs)


def build_cell(cfg: ModelConfig, shape_name: str, mesh,
               grad_compression: str | None = None):
    """Returns (fn, args_sds, in_shardings, donate) for lower()."""
    plan = build_plan(cfg, shape_name, mesh)
    sh = configs.SHAPES[shape_name]
    specs = configs.input_specs(cfg, shape_name)

    if sh.kind == "train":
        opt = get_optimizer(cfg.optimizer)
        state = abstract_train_state(cfg, opt)
        step = make_train_step(cfg, opt, grad_compression=grad_compression)
        state_sh = {"params": param_shardings(plan, state["params"]),
                    "opt": param_shardings(plan, state["opt"]),
                    "step": jax.sharding.NamedSharding(
                        mesh, jax.sharding.PartitionSpec())}
        args = (state, specs)
        in_sh = (state_sh, _batch_shardings(plan, specs))
        return plan, step, args, in_sh, (0,)

    params = T.abstract_params(cfg)
    p_sh = param_shardings(plan, params)

    if sh.kind == "prefill":
        if not cfg.has_decode:
            # encoder-only arch: prefill_32k lowers the encode step
            def encode_step(params, batch):
                logits, _, _ = T.forward(cfg, params, batch)
                return logits
            return (plan, encode_step, (params, specs),
                    (p_sh, _batch_shardings(plan, specs)), ())

        def prefill_step(params, batch):
            cache, logits = T.prefill(cfg, params, batch, max_len=sh.seq_len)
            # shard the returned cache (kv_seq -> model): without this the
            # cache comes out batch-sharded only (16x per-device blowup)
            cache_sh = _cache_shardings(plan, cache)
            cache = jax.tree.map(jax.lax.with_sharding_constraint,
                                 cache, cache_sh)
            return cache, logits
        args = (params, specs)
        in_sh = (p_sh, _batch_shardings(plan, specs))
        return plan, prefill_step, args, in_sh, ()

    # decode: scan over layers with DEFERRED cache commit — the cache is a
    # read-only scan input; each layer emits just its new [B,1,K,D] entry
    # and one batched aliased scatter commits after the scan, so no cache
    # double-buffer rides the loop carry (see models/transformer.py)

    def serve_step(params, cache, tokens):
        new_cache, logits = T.decode_step(cfg, params, cache, tokens)
        cache_sh = _cache_shardings(plan, new_cache)
        new_cache = jax.tree.map(jax.lax.with_sharding_constraint,
                                 new_cache, cache_sh)
        return new_cache, logits
    cache_sh = _cache_shardings(plan, specs["cache"])
    tok_sh = plan.sharding("batch")
    args = (params, specs["cache"], specs["tokens"])
    in_sh = (p_sh, cache_sh, tok_sh)
    return plan, serve_step, args, in_sh, (1,)


def _compile_and_measure(cfg: ModelConfig, shape_name: str, mesh,
                         grad_compression: str | None = None) -> dict:
    t0 = time.perf_counter()
    plan, fn, args, in_sh, donate = build_cell(cfg, shape_name, mesh,
                                               grad_compression)
    with use_plan(plan), mesh:
        jf = jax.jit(fn, in_shardings=in_sh, donate_argnums=donate)
        lowered = jf.lower(*args)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
    census = collective_census(hlo)
    return {
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "peak_device_bytes": int(mem.argument_size_in_bytes
                                     + mem.output_size_in_bytes
                                     + mem.temp_size_in_bytes
                                     - mem.alias_size_in_bytes),
        },
        "cost": {"flops_per_device": float(cost.get("flops", 0.0)),
                 "bytes_per_device": float(cost.get("bytes accessed", 0.0))},
        "collectives": census,
    }


# layer counts for the two unrolled cost probes, per family (hybrid uses
# multiples of attn_every so each probe has whole shared-block applications)
def _probe_layers(cfg: ModelConfig) -> tuple[int, int]:
    if cfg.family == "hybrid":
        return cfg.attn_every, 2 * cfg.attn_every
    return 2, 4


def extrapolate_cost(cfg: ModelConfig, shape_name: str, mesh) -> dict:
    """True whole-model FLOPs/bytes/collectives per device.

    XLA's cost_analysis counts a while-loop (scan) body ONCE regardless of
    trip count, so the fit-variant numbers undercount layers.  We compile
    two small UNROLLED variants (L0 < L1 layers, no grad-accum scan) and
    extrapolate the per-layer delta to the real depth:

        cost(L) = cost(L1) + (L - L1) * (cost(L1) - cost(L0)) / (L1 - L0)

    Grad accumulation is FLOP-neutral (same tokens, one optimizer update),
    so the probes run microbatch=1.
    """
    L0, L1 = _probe_layers(cfg)
    probes = []
    for Lp in (L0, L1):
        cfg_p = cfg.replace(n_layers=Lp, scan_layers=False, microbatch=1)
        probes.append(_compile_and_measure(cfg_p, shape_name, mesh))

    def lin(get):
        c0, c1 = get(probes[0]), get(probes[1])
        per_layer = (c1 - c0) / (L1 - L0)
        return c1 + per_layer * (cfg.n_layers - L1), per_layer

    flops, flops_l = lin(lambda p: p["cost"]["flops_per_device"])
    byts, bytes_l = lin(lambda p: p["cost"]["bytes_per_device"])
    wire, wire_l = lin(
        lambda p: p["collectives"]["wire_bytes_per_device"])
    ncoll, _ = lin(lambda p: float(p["collectives"]["n_collectives"]))
    return {
        "method": f"unrolled probes L={L0},{L1} -> L={cfg.n_layers}",
        "flops_per_device": flops, "flops_per_layer_device": flops_l,
        "bytes_per_device": byts, "bytes_per_layer_device": bytes_l,
        "collective_wire_bytes_per_device": wire,
        "collective_wire_bytes_per_layer": wire_l,
        "n_collectives_est": ncoll,
        "probe_compile_s": [p["compile_s"] for p in probes],
        "probe_by_op": probes[1]["collectives"]["by_op"],
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             grad_compression: str | None = None,
             variant: str = "baseline", with_cost: bool = True,
             cfg: ModelConfig | None = None) -> dict:
    if cfg is None:
        cfg = configs.get(arch)
    ok, why = configs.cell_supported(cfg, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    mesh = make_production_mesh(multi_pod=multi_pod)
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "variant": variant, "n_devices": int(mesh.devices.size),
        "config": {"family": cfg.family, "params": cfg.param_count(),
                   "params_active": cfg.param_count(active_only=True),
                   "microbatch": cfg.microbatch, "fsdp": cfg.fsdp,
                   "optimizer": cfg.optimizer},
    }
    result.update(_compile_and_measure(cfg, shape_name, mesh,
                                       grad_compression))
    if with_cost:
        result["cost_extrapolated"] = extrapolate_cost(cfg, shape_name, mesh)
    return result


def artifact_path(arch: str, shape: str, mesh_name: str,
                  variant: str = "baseline") -> str:
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    v = "" if variant == "baseline" else f"__{variant}"
    return os.path.join(ARTIFACT_DIR, f"{arch}__{shape}__{mesh_name}{v}.json")


def main():
    # 512 fake host devices for the production meshes; XLA reads the
    # flag when jax first queries its devices, so it is set here and
    # never when the module is imported
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                               + os.environ.get("XLA_FLAGS", ""))
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--grad-compression", default=None)
    # §Perf hillclimb knobs (recorded under --variant artifacts)
    ap.add_argument("--profile", default=None,
                    choices=[None, "tp_sp", "fsdp_only", "fsdp_ep"])
    ap.add_argument("--kv-dtype", default=None,
                    choices=[None, "bfloat16", "int8"])
    ap.add_argument("--set", action="append", default=[],
                    help="cfg overrides, e.g. --set microbatch=4")
    args = ap.parse_args()

    def cfg_for(arch):
        cfg = configs.get(arch)
        if args.profile:
            cfg = cfg.replace(sharding_profile=args.profile)
        if args.kv_dtype:
            cfg = cfg.replace(kv_cache_dtype=args.kv_dtype)
        for kv in args.set:
            k, v = kv.split("=", 1)
            cur = getattr(cfg, k)
            if isinstance(cur, bool):
                v = v.lower() in ("1", "true", "yes")
            elif isinstance(cur, int):
                v = int(v)
            elif isinstance(cur, float):
                v = float(v)
            cfg = cfg.replace(**{k: v})
        return cfg

    if args.all:
        cells = configs.all_cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for arch, shape in cells:
        for mp in meshes:
            mesh_name = "pod2x16x16" if mp else "pod16x16"
            path = artifact_path(arch, shape, mesh_name, args.variant)
            if os.path.exists(path) and not args.force:
                print(f"[cached] {arch} {shape} {mesh_name}")
                continue
            print(f"[lower+compile] {arch} {shape} {mesh_name} ...",
                  flush=True)
            try:
                # roofline probes are single-pod only (the table's scope);
                # the multi-pod pass proves the "pod" axis shards.
                res = run_cell(arch, shape, mp,
                               grad_compression=args.grad_compression,
                               variant=args.variant, with_cost=not mp,
                               cfg=cfg_for(arch))
            except Exception as e:
                traceback.print_exc()
                failures.append((arch, shape, mesh_name, str(e)))
                continue
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
            if "skipped" in res:
                print(f"  skipped: {res['skipped']}")
            else:
                m = res["memory"]
                print(f"  compile={res['compile_s']}s "
                      f"peak/dev={m['peak_device_bytes']/2**30:.2f}GiB "
                      f"flops/dev={res['cost']['flops_per_device']:.3g} "
                      f"coll/dev={res['collectives']['wire_bytes_per_device']/2**30:.3f}GiB")
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f4 in failures:
            print("  ", *f4)
        raise SystemExit(1)
    print("\nall requested cells compiled OK")


if __name__ == "__main__":
    main()
