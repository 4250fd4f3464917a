import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first initialization). Everything else follows.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell on the production meshes, with 512 placeholder host devices.

For each cell this produces (appended incrementally to a JSON results file):
  * compile success on the 16x16 single-pod mesh AND the 2x16x16 multi-pod
    mesh (the multi-pod pass proves the 'pod' axis shards),
  * ``memory_analysis()`` per-device byte accounting (proves it fits),
  * ``cost_analysis()`` FLOPs/bytes (per-device, post-partitioning),
  * per-collective byte counts parsed from the compiled HLO,
  * the same three quantities for the *accounting* compiles (one scan unit,
    the embed/head step, the optimizer step) — XLA counts while-loop bodies
    once, so the roofline multiplies the unit terms by n_units (see
    launch/roofline.py).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh pod1|pod2|both]
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.launch import steps as St
from repro.launch.hlo import (collective_bytes, cost_analysis_dict,
                              count_hlo_ops)
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.models.common import dtype_of
from repro.optim import init_state
from repro.sharding import make_rules, param_sharding, use_rules

RESULTS_PATH = "experiments/dryrun_results.json"

# Archs that cannot run the long_500k cell (pure full-attention; DESIGN.md
# §7 records the skip rationale).
LONG_CONTEXT_OK = {"xlstm_350m", "zamba2_1p2b"}


def train_overrides(arch_id: str) -> TrainConfig:
    """Per-arch numerics needed to fit the assigned mesh (DESIGN.md §6)."""
    if arch_id == "qwen3_moe_235b_a22b":
        return TrainConfig(moment_dtype="bfloat16")  # optimizer compression
    return TrainConfig()


def model_overrides(arch_id: str, cfg: ModelConfig,
                    shape: ShapeConfig) -> ModelConfig:
    if arch_id == "qwen3_moe_235b_a22b":
        cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
    if shape.kind != "train" and shape.seq_len >= 32768:
        # prefill/decode at 32k+: keep flash blocks modest
        cfg = dataclasses.replace(cfg, flash_block=1024)
    return cfg


def _mem_dict(ma) -> Dict[str, float]:
    if ma is None:
        return {}
    return {
        "argument_bytes": float(ma.argument_size_in_bytes),
        "output_bytes": float(ma.output_size_in_bytes),
        "temp_bytes": float(ma.temp_size_in_bytes),
        "alias_bytes": float(ma.alias_size_in_bytes),
        "peak_bytes_est": float(ma.argument_size_in_bytes
                                + ma.output_size_in_bytes
                                + ma.temp_size_in_bytes
                                - ma.alias_size_in_bytes),
    }


def _cost_dict(ca: Dict[str, float]) -> Dict[str, float]:
    if not ca:
        return {}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "transcendentals": float(ca.get("transcendentals", 0.0))}


def _analyze(compiled) -> Dict[str, Any]:
    txt = compiled.as_text()
    return {
        "memory": _mem_dict(compiled.memory_analysis()),
        "cost": _cost_dict(cost_analysis_dict(compiled)),
        "collectives": collective_bytes(txt),
        "hlo_ops": count_hlo_ops(txt),
    }


def apply_overrides(cfg: ModelConfig, overrides: str) -> ModelConfig:
    """--override "a=b,ffn_sparsity.n=8,..." -> dataclasses.replace chain.

    Nested SparsityConfig fields use dotted paths; values are parsed as
    python literals when possible."""
    import ast
    for item in overrides.split(","):
        if not item:
            continue
        key, _, val = item.partition("=")
        try:
            val = ast.literal_eval(val)
        except (ValueError, SyntaxError):
            pass
        if "." in key:
            outer, inner = key.split(".", 1)
            sub = dataclasses.replace(getattr(cfg, outer), **{inner: val})
            cfg = dataclasses.replace(cfg, **{outer: sub})
        else:
            cfg = dataclasses.replace(cfg, **{key: val})
    return cfg


def compile_cell(arch_id: str, shape_name: str, multi_pod: bool,
                 accounting: bool = True,
                 cfg_override=None, tcfg_override=None,
                 overrides: str = "") -> Dict[str, Any]:
    """Lower+compile one cell; returns the result record."""
    cfg = cfg_override or get_config(arch_id)
    shape = SHAPES[shape_name]
    cfg = model_overrides(arch_id, cfg, shape) if cfg_override is None else cfg
    if overrides:
        cfg = apply_overrides(cfg, overrides)
    tcfg = tcfg_override or train_overrides(arch_id)
    mesh = make_production_mesh(multi_pod=multi_pod)
    kind = shape.kind
    if kind == "decode" and shape.global_batch < 8:
        kind = "decode_long"
    rules = make_rules(mesh, kind)
    rec: Dict[str, Any] = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind, "n_units": cfg.n_units,
        "pattern": list(cfg.block_pattern),
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "time": {},
    }
    t0 = time.time()

    with use_rules(rules):
        params_s, specs = St.abstract_params(cfg)
        p_shard = param_sharding(specs, params_s, rules)
        batch = St.input_specs(cfg, shape)
        b_shard = {k: rules.sharding_for(v, batch[k].shape)
                   for k, v in St.batch_logical_specs(batch).items()}
        n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params_s)
                       if jnp.issubdtype(x.dtype, jnp.floating))
        rec["n_params"] = n_params

        if shape.kind == "train":
            train_step, acfg = St.make_train_step(cfg, tcfg)
            opt_s = jax.eval_shape(lambda p: init_state(p, acfg), params_s)
            o_specs = {"mu": specs, "nu": specs, "step": ()}
            if tcfg.zero1:
                zspecs = St.zero1_specs(specs, params_s, rules)
                o_specs = {"mu": zspecs, "nu": zspecs, "step": ()}
            o_shard = {
                "mu": param_sharding(o_specs["mu"], opt_s["mu"], rules),
                "nu": param_sharding(o_specs["nu"], opt_s["nu"], rules),
                "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            }
            jitted = jax.jit(train_step,
                             in_shardings=(p_shard, o_shard, b_shard),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_s, opt_s, batch)
            rec["time"]["lower"] = time.time() - t0
            compiled = lowered.compile()
            rec["time"]["compile"] = time.time() - t0 - rec["time"]["lower"]
            rec["full"] = _analyze(compiled)
            if accounting:
                rec.update(_accounting_train(cfg, tcfg, shape, mesh, rules,
                                             params_s, specs))
        elif shape.kind == "prefill":
            step = St.make_prefill_step(cfg)
            jitted = jax.jit(step, in_shardings=(p_shard, b_shard))
            lowered = jitted.lower(params_s, batch)
            rec["time"]["lower"] = time.time() - t0
            compiled = lowered.compile()
            rec["time"]["compile"] = time.time() - t0 - rec["time"]["lower"]
            rec["full"] = _analyze(compiled)
            if accounting:
                rec.update(_accounting_fwd(cfg, shape, mesh, rules,
                                           params_s, specs))
        else:  # decode
            step = St.make_serve_step(cfg)
            cache_s, c_specs = St.abstract_cache(cfg, shape.global_batch,
                                                 shape.seq_len)
            c_shard = param_sharding(c_specs, cache_s, rules)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            jitted = jax.jit(step,
                             in_shardings=(p_shard, c_shard, b_shard, None),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_s, cache_s, batch, pos)
            rec["time"]["lower"] = time.time() - t0
            compiled = lowered.compile()
            rec["time"]["compile"] = time.time() - t0 - rec["time"]["lower"]
            rec["full"] = _analyze(compiled)
            if accounting:
                rec.update(_accounting_decode(cfg, shape, mesh, rules,
                                              params_s, specs, cache_s,
                                              c_specs))
    rec["time"]["total"] = time.time() - t0
    rec["ok"] = True
    return rec


# ---------------------------------------------------------------------------
# Accounting compiles (per-unit / head / optimizer)
# ---------------------------------------------------------------------------

def _unit_slice(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), tree)


def _acc_seq(cfg, shape) -> int:
    """Accounting sequence length: SSM-family units cost linearly in T (the
    chunk scan), so compile them at <=4096 and let the roofline scale by
    T/T_acc — full-T unrolled SSD compiles (256 chunks) take tens of
    minutes on this host. Attention-family units keep full T (quadratic).
    zamba2's single shared_attn gets an analytic quadratic correction in
    roofline.py."""
    ssm = any(k in ("mamba2", "mlstm", "slstm") for k in cfg.block_pattern)
    if ssm and shape.seq_len > 4096:
        return 4096
    return shape.seq_len


def _x_specs(cfg, shape, rules, seq=None):
    ct = dtype_of(cfg.compute_dtype)
    seq = seq or shape.seq_len
    x = jax.ShapeDtypeStruct((shape.global_batch, seq, cfg.d_model), ct)
    pos = jax.ShapeDtypeStruct((shape.global_batch, seq), jnp.int32)
    x_sh = rules.sharding_for(("batch", "seq", None), x.shape)
    pos_sh = rules.sharding_for(("batch", "seq"), pos.shape)
    return x, pos, x_sh, pos_sh


def _accounting_train(cfg, tcfg, shape, mesh, rules, params_s, specs):
    out = {}
    # one scan unit, fwd+bwd
    acc_cfg = dataclasses.replace(cfg, remat=False, unroll_inner=True)
    unit_step = St.make_unit_train_step(acc_cfg)
    up_s = _unit_slice(params_s["units"])
    up_specs = jax.tree.map(lambda s: tuple(s[1:]), specs["units"],
                            is_leaf=St._spec_leaf)
    up_shard = param_sharding(up_specs, up_s, rules)
    shared_s = params_s.get("shared")
    sh_shard = param_sharding(specs["shared"], shared_s, rules) \
        if shared_s is not None else None
    seq_acc = _acc_seq(cfg, shape)
    x, pos, x_sh, pos_sh = _x_specs(cfg, shape, rules, seq=seq_acc)
    jitted = jax.jit(unit_step,
                     in_shardings=(up_shard, sh_shard, x_sh, pos_sh))
    compiled = jitted.lower(up_s, shared_s, x, pos).compile()
    out["unit"] = _analyze(compiled)
    out["unit"]["scale_T"] = shape.seq_len / seq_acc
    out["unit"]["acc_seq"] = seq_acc

    # embed + head + loss fwd+bwd (always full T)
    x, pos, x_sh, pos_sh = _x_specs(cfg, shape, rules)
    head_step = St.make_head_train_step(cfg)
    table = params_s["embed"]["table"]
    tok = jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len), jnp.int32)
    t_sh = rules.sharding_for(("vocab", "embed"), table.shape)
    tok_sh = rules.sharding_for(("batch", None), tok.shape)
    compiled = jax.jit(head_step,
                       in_shardings=(t_sh, tok_sh, tok_sh, x_sh)).lower(
        table, tok, tok, x).compile()
    out["head"] = _analyze(compiled)

    # optimizer step
    opt_step = St.make_opt_step(cfg, tcfg)
    from repro.optim import AdamWConfig
    acfg = AdamWConfig(moment_dtype=dtype_of(tcfg.moment_dtype))
    opt_s = jax.eval_shape(lambda p: init_state(p, acfg), params_s)
    o_specs_m = St.zero1_specs(specs, params_s, rules) if tcfg.zero1 else specs
    p_shard = param_sharding(specs, params_s, rules)
    o_shard = {"mu": param_sharding(o_specs_m, opt_s["mu"], rules),
               "nu": param_sharding(o_specs_m, opt_s["nu"], rules),
               "step": jax.sharding.NamedSharding(
                   mesh, jax.sharding.PartitionSpec())}
    compiled = jax.jit(opt_step,
                       in_shardings=(p_shard, p_shard, o_shard),
                       donate_argnums=(0, 2)).lower(
        params_s, params_s, opt_s).compile()
    out["opt"] = _analyze(compiled)
    return out


def _accounting_fwd(cfg, shape, mesh, rules, params_s, specs):
    out = {}
    acc_cfg = dataclasses.replace(cfg, remat=False, unroll_inner=True)
    unit_step = St.make_unit_fwd_step(acc_cfg)
    up_s = _unit_slice(params_s["units"])
    up_specs = jax.tree.map(lambda s: tuple(s[1:]), specs["units"],
                            is_leaf=St._spec_leaf)
    up_shard = param_sharding(up_specs, up_s, rules)
    shared_s = params_s.get("shared")
    sh_shard = param_sharding(specs["shared"], shared_s, rules) \
        if shared_s is not None else None
    seq_acc = _acc_seq(cfg, shape)
    x, pos, x_sh, pos_sh = _x_specs(cfg, shape, rules, seq=seq_acc)
    compiled = jax.jit(unit_step,
                       in_shardings=(up_shard, sh_shard, x_sh, pos_sh)).lower(
        up_s, shared_s, x, pos).compile()
    out["unit"] = _analyze(compiled)
    out["unit"]["scale_T"] = shape.seq_len / seq_acc
    out["unit"]["acc_seq"] = seq_acc

    ct = dtype_of(cfg.compute_dtype)
    x, pos, x_sh, pos_sh = _x_specs(cfg, shape, rules)
    table = params_s["embed"]["table"]
    t_sh = rules.sharding_for(("vocab", "embed"), table.shape)

    def head_fwd(table, x):
        return (x @ table.astype(ct).T)[:, -1]

    x_sh2 = rules.sharding_for(("batch", "seq", None), x.shape)
    compiled = jax.jit(head_fwd, in_shardings=(t_sh, x_sh2)).lower(
        table, x).compile()
    out["head"] = _analyze(compiled)
    return out


def _accounting_decode(cfg, shape, mesh, rules, params_s, specs, cache_s,
                       c_specs):
    """One-unit decode step + head projection."""
    out = {}
    unit_cache = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), cache_s)
    uc_specs = jax.tree.map(lambda s: tuple(s[1:]), c_specs,
                            is_leaf=St._spec_leaf)
    uc_shard = param_sharding(uc_specs, unit_cache, rules)
    up_s = _unit_slice(params_s["units"])
    up_specs = jax.tree.map(lambda s: tuple(s[1:]), specs["units"],
                            is_leaf=St._spec_leaf)
    up_shard = param_sharding(up_specs, up_s, rules)
    shared_s = params_s.get("shared")
    sh_shard = param_sharding(specs["shared"], shared_s, rules) \
        if shared_s is not None else None
    ct = dtype_of(cfg.compute_dtype)
    x = jax.ShapeDtypeStruct((shape.global_batch, 1, cfg.d_model), ct)
    x_sh = rules.sharding_for(("batch", None, None), x.shape)
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    from repro.models.transformer import _block_decode

    def unit_decode(unit_params, shared, x, unit_cache, pos):
        new_cache = {}
        for i, kind in enumerate(cfg.block_pattern):
            p = shared if kind == "shared_attn" else unit_params[f"b{i}"]
            x, new_cache[f"b{i}"], _ = _block_decode(
                kind, p, x, cfg, unit_cache[f"b{i}"], pos)
        return x, new_cache

    compiled = jax.jit(unit_decode,
                       in_shardings=(up_shard, sh_shard, x_sh, uc_shard, None),
                       donate_argnums=(3,)).lower(
        up_s, shared_s, x, unit_cache, pos).compile()
    out["unit"] = _analyze(compiled)

    table = params_s["embed"]["table"]
    t_sh = rules.sharding_for(("vocab", "embed"), table.shape)

    def head_fwd(table, x):
        return (x @ table.astype(ct).T)[:, 0]

    compiled = jax.jit(head_fwd, in_shardings=(t_sh, x_sh)).lower(
        table, x).compile()
    out["head"] = _analyze(compiled)
    return out


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def iter_cells(mesh_sel: str):
    for arch in ARCH_IDS:
        for shape in SHAPES:
            if shape == "long_500k" and arch not in LONG_CONTEXT_OK:
                continue
            for mp in ([False, True] if mesh_sel == "both"
                       else [mesh_sel == "pod2"]):
                yield arch, shape, mp


def load_results(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}


def save_results(path: str, results: Dict[str, Any]) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(results, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["pod1", "pod2", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-accounting", action="store_true")
    ap.add_argument("--override", default="",
                    help="cfg overrides, e.g. kv_cache_dtype=int8,"
                         "ffn_sparsity.route_share=64")
    ap.add_argument("--tag", default="", help="suffix for the result key")
    ap.add_argument("--out", default=RESULTS_PATH)
    args = ap.parse_args()

    results = load_results(args.out)
    if args.all:
        cells = list(iter_cells(args.mesh))
    else:
        archs = [args.arch] if args.arch else ARCH_IDS
        shapes = [args.shape] if args.shape else list(SHAPES)
        cells = [(a, s, mp) for a in archs for s in shapes
                 for mp in ([False, True] if args.mesh == "both"
                            else [args.mesh == "pod2"])
                 if not (s == "long_500k"
                         and a.replace("-", "_") not in LONG_CONTEXT_OK
                         and a not in LONG_CONTEXT_OK)]

    for arch, shape, mp in cells:
        arch_id = arch.replace("-", "_").replace(".", "p")
        key = f"{arch_id}|{shape}|{'pod2' if mp else 'pod1'}"
        if args.tag:
            key += f"|{args.tag}"
        if key in results and results[key].get("ok") and not args.force:
            print(f"[skip] {key}")
            continue
        print(f"[run ] {key}", flush=True)
        t0 = time.time()
        try:
            rec = compile_cell(arch_id, shape, mp,
                               accounting=not args.no_accounting,
                               overrides=args.override)
        except Exception as e:  # noqa: BLE001 — record failures, keep going
            rec = {"arch": arch_id, "shape": shape,
                   "mesh": "2x16x16" if mp else "16x16", "ok": False,
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
        rec["wall_s"] = time.time() - t0
        results[key] = rec
        save_results(args.out, results)
        status = "OK" if rec.get("ok") else "FAIL"
        print(f"[{status:4s}] {key} ({rec['wall_s']:.1f}s)", flush=True)

    n_ok = sum(1 for r in results.values() if r.get("ok"))
    print(f"done: {n_ok}/{len(results)} cells ok")


if __name__ == "__main__":
    main()
