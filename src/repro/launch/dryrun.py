import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first
# backend initialization.  (Do not set this anywhere global — smoke tests
# and benches must keep seeing 1 device.)

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the real jitted computation (train_step for
train shapes; prefill / decode serve_step for inference shapes) against
the production mesh, with in/out shardings from the ShardingPolicy, then:

    lowered  = jax.jit(fn, in_shardings=..., out_shardings=...).lower(*specs)
    compiled = lowered.compile()
    print(compiled.memory_analysis())   # proves the cell fits
    print(compiled.cost_analysis())     # FLOPs/bytes for §Roofline

and extracts collective bytes from the post-SPMD HLO for the roofline's
collective term.  Results land in ``results/dryrun/<cell>.json`` which
``benchmarks/roofline.py`` consumes.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
        --shape train_4k [--multi-pod] [--all] [--out results/dryrun]
"""
import argparse
import dataclasses
import functools
import json
import re
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.models.api import SHAPES, ArchModel, ShapeSpec, build_model, input_specs
from repro.models.config import ModelConfig
from repro.optim import make_optimizer
from repro.parallel.act_sharding import activation_sharding
from repro.parallel.policy import ShardingPolicy
from repro.training.train_step import TrainState, init_train_state, make_train_step

# Archs whose long_500k cell is skipped: pure full-attention families
# (quadratic attention at 524288 is out of scope by assignment; see
# DESIGN §Arch-applicability).
LONG_OK = {"h2o-danube-1.8b", "falcon-mamba-7b", "jamba-1.5-large-398b"}


def cell_id(arch: str, shape: str, multi_pod: bool) -> str:
    return f"{arch}__{shape}__{'pod2' if multi_pod else 'pod1'}"


def pick_optimizer(cfg: ModelConfig) -> Any:
    """Adafactor for 100B+ (state must fit the pod), AdamW otherwise."""
    big = cfg.param_count() > 50e9
    return make_optimizer("adafactor" if big else "adamw")


def _sharding_tree(policy: ShardingPolicy, spec_tree):
    return jax.tree.map(policy.named, spec_tree,
                        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))


# --------------------------------------------------------------- lowering

def lower_train(model: ArchModel, policy: ShardingPolicy, shape: ShapeSpec,
                grad_accum: int = 1):
    cfg = model.cfg
    optimizer = pick_optimizer(cfg)
    step_fn = make_train_step(model, optimizer, grad_accum=grad_accum)

    state_shapes = jax.eval_shape(
        lambda k: init_train_state(model, optimizer, k), jax.random.key(0))
    state_specs = policy.tree_specs(state_shapes)
    batch_shapes = input_specs(cfg, shape)
    batch_specs = policy.batch_spec(batch_shapes)

    jitted = jax.jit(
        step_fn,
        in_shardings=(_sharding_tree(policy, state_specs),
                      _sharding_tree(policy, batch_specs)),
        out_shardings=(_sharding_tree(policy, state_specs), None),
        donate_argnums=(0,),
    )
    return jitted.lower(state_shapes, batch_shapes)


def lower_prefill(model: ArchModel, policy: ShardingPolicy,
                  shape: ShapeSpec):
    cfg = model.cfg
    params_shapes = jax.eval_shape(model.init, jax.random.key(0))
    param_specs = policy.tree_specs(params_shapes)
    batch_shapes = input_specs(cfg, shape)
    batch_specs = policy.batch_spec(batch_shapes)
    cache_shapes = jax.eval_shape(
        functools.partial(model.init_cache, shape.global_batch,
                          shape.seq_len))
    cache_specs = policy.cache_spec(cache_shapes)

    def prefill_fn(params, batch):
        return model.prefill(params, batch, max_len=shape.seq_len)

    jitted = jax.jit(
        prefill_fn,
        in_shardings=(_sharding_tree(policy, param_specs),
                      _sharding_tree(policy, batch_specs)),
        out_shardings=(None, _sharding_tree(policy, cache_specs)),
    )
    return jitted.lower(params_shapes, batch_shapes)


def lower_decode(model: ArchModel, policy: ShardingPolicy,
                 shape: ShapeSpec):
    """serve_step: one new token against a cache of seq_len.

    Lowered inside serve-mode activation sharding: batch-replicated
    activations + 2D-sharded weights (see act_sharding docstring).
    """
    cfg = model.cfg
    params_shapes = jax.eval_shape(model.init, jax.random.key(0))
    param_specs = policy.tree_specs(params_shapes)
    cache_shapes = jax.eval_shape(
        functools.partial(model.init_cache, shape.global_batch,
                          shape.seq_len))
    cache_specs = policy.cache_spec(cache_shapes)
    tok_shapes = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    from jax.sharding import PartitionSpec as P

    tok_spec = P(None, None)  # serve mode: batch replicated

    def serve_step(params, tokens, cache):
        return model.decode_step(params, tokens, cache)

    jitted = jax.jit(
        serve_step,
        in_shardings=(_sharding_tree(policy, param_specs),
                      policy.named(tok_spec),
                      _sharding_tree(policy, cache_specs)),
        out_shardings=(None, _sharding_tree(policy, cache_specs)),
        donate_argnums=(2,),
    )
    return jitted.lower(params_shapes, tok_shapes, cache_shapes)


# -------------------------------------------------------------- analysis

COLLECTIVE_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum result bytes of every collective op in the (post-SPMD) HLO."""
    out: Dict[str, float] = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        result_ty, kind = m.group(1), m.group(2)
        nbytes = 0
        for dm in SHAPE_RE.finditer(result_ty):
            dt, dims = dm.group(1), dm.group(2)
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0.0) + float(nbytes)
    return out


def cost_dict(compiled) -> Dict[str, float]:
    """``Compiled.cost_analysis()``, or ``{}`` where the backend gives
    none."""
    return compiled.cost_analysis() or {}


def analyse(lowered, compiled) -> Dict[str, Any]:
    cost = cost_dict(compiled)
    mem = compiled.memory_analysis()
    mem_info: Dict[str, Any] = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes"):
        if hasattr(mem, attr):
            mem_info[attr] = int(getattr(mem, attr))
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    return {
        "flops": float(cost.get("flops", -1.0)),
        "bytes_accessed": float(cost.get("bytes accessed", -1.0)),
        "transcendentals": float(cost.get("transcendentals", 0.0)),
        "memory": mem_info,
        "collectives": coll,
        "collective_bytes_total": float(sum(coll.values())),
    }


# ----------------------------------------------------------- calibration
#
# XLA's HLO cost analysis visits while-loop (lax.scan) bodies ONCE, so a
# scanned L-layer stack under-reports flops/bytes by ~L×.  We calibrate by
# lowering the same cell with the stack UNROLLED at two small depths n1<n2
# (in units of the arch's repeating group) and extrapolating linearly:
#       m(n) = a + b·n   =>   m(L_true) = m(n1) + (m(n2)-m(n1))·(L−n1)/(n2−n1)
# The full scan-based compile remains the deployable artifact (its
# memory_analysis is what we report); calibration only fixes the counters.

def _calib_configs(cfg: ModelConfig):
    """Return (n1_cfg, n2_cfg, n1, n2, n_true) in group units."""
    r = dataclasses.replace
    if cfg.is_hybrid:
        g = cfg.hybrid_group
        return (r(cfg, num_layers=g, scan_layers=False),
                r(cfg, num_layers=2 * g, scan_layers=False),
                1, 2, cfg.num_layers // g)
    if cfg.is_vlm:
        e = cfg.cross_attn_every
        return (r(cfg, num_layers=e, scan_layers=False),
                r(cfg, num_layers=2 * e, scan_layers=False),
                1, 2, cfg.num_layers // e)
    if cfg.is_encdec:
        return (r(cfg, num_layers=1, encoder_layers=1, scan_layers=False),
                r(cfg, num_layers=2, encoder_layers=2, scan_layers=False),
                1, 2, cfg.num_layers)
    extra = 1 if cfg.first_layer_dense_ff > 0 else 0
    n_true = cfg.num_layers - extra
    return (r(cfg, num_layers=1 + extra, scan_layers=False),
            r(cfg, num_layers=2 + extra, scan_layers=False),
            1, 2, n_true)


def _cell_costs(cfg: ModelConfig, policy: ShardingPolicy, shape: ShapeSpec,
                grad_accum: int) -> Dict[str, float]:
    model = build_model(cfg)
    if shape.kind == "train":
        lowered = lower_train(model, policy, shape, grad_accum=grad_accum)
    elif shape.kind == "prefill":
        lowered = lower_prefill(model, policy, shape)
    else:
        lowered = lower_decode(model, policy, shape)
    compiled = lowered.compile()
    cost = cost_dict(compiled)
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "transcendentals": float(cost.get("transcendentals", 0.0)),
        "collective_bytes_total": float(sum(coll.values())),
        **{f"coll_{k}": v for k, v in coll.items()},
    }


def calibrate(cfg: ModelConfig, policy: ShardingPolicy, shape: ShapeSpec,
              grad_accum: int = 1) -> Dict[str, Any]:
    cfg1, cfg2, n1, n2, n_true = _calib_configs(cfg)
    m1 = _cell_costs(cfg1, policy, shape, grad_accum)
    m2 = _cell_costs(cfg2, policy, shape, grad_accum)
    out: Dict[str, Any] = {"n1": n1, "n2": n2, "n_true": n_true}
    for k in set(m1) | set(m2):
        a, b = m1.get(k, 0.0), m2.get(k, 0.0)
        out[k] = a + (b - a) * (n_true - n1) / (n2 - n1)
        out[f"{k}_n1"] = a
        out[f"{k}_n2"] = b
    return out


# ------------------------------------------------------------------ cells

def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = "results/dryrun",
             grad_accum: int = 1,
             calibrate_costs: bool = True,
             sp: bool = False,
             remat_policy: Optional[str] = None) -> Dict[str, Any]:
    cfg = get_config(arch)
    if remat_policy:
        cfg = dataclasses.replace(cfg, remat_policy=remat_policy)
    if grad_accum == 0:  # auto: microbatch the 50B+ models (fit HBM)
        grad_accum = 16 if cfg.param_count() > 50e9 else 1
    shape = SHAPES[shape_name]
    result: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "cell": cell_id(arch, shape_name, multi_pod),
    }

    if shape_name == "long_500k" and arch not in LONG_OK:
        result["status"] = "skipped"
        result["reason"] = ("pure full-attention arch: long_500k requires "
                            "sub-quadratic attention (DESIGN "
                            "§Arch-applicability)")
        _save(result, out_dir)
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    policy = ShardingPolicy(mesh)
    model = build_model(cfg)

    t0 = time.time()
    try:
        with mesh, activation_sharding(policy, sp=sp,
                                       serve=(shape.kind == "decode")):
            if shape.kind == "train":
                lowered = lower_train(model, policy, shape,
                                      grad_accum=grad_accum)
            elif shape.kind == "prefill":
                lowered = lower_prefill(model, policy, shape)
            else:
                lowered = lower_decode(model, policy, shape)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        print(compiled.memory_analysis())
        print(compiled.cost_analysis())
        result.update(analyse(lowered, compiled))
        result["status"] = "ok"
        result["lower_s"] = round(t_lower, 2)
        result["compile_s"] = round(t_compile, 2)
        result["sharding_fallbacks"] = policy.fallbacks
        nd = len(mesh.devices.flatten())
        result["num_devices"] = nd
        if calibrate_costs:
            # NOTE: cost calibration always runs at grad_accum=1 — the
            # microbatch lax.scan hides its body from HLO cost analysis
            # exactly like layer scans, and per-step math is ga-invariant.
            # memory_analysis above reflects the requested grad_accum.
            with mesh, activation_sharding(policy, sp=sp,
                                           serve=(shape.kind == "decode")):
                result["calibrated"] = calibrate(cfg, policy, shape,
                                                 grad_accum=1)
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]
    _save(result, out_dir)
    return result


def _save(result: Dict[str, Any], out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, result["cell"] + ".json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS, default=None)
    ap.add_argument("--shape", choices=list(SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) for the chosen mesh")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--grad-accum", type=int, default=0,
                    help="0 = auto (16 for 50B+ models, else 1)")
    ap.add_argument("--sp", action="store_true",
                    help="Megatron-SP residual sharding (train cells)")
    ap.add_argument("--remat-policy", default=None,
                    choices=["full", "dots"])
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in (ARCH_IDS if args.arch is None else [args.arch]):
            for shape in (SHAPES if args.shape is None else [args.shape]):
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape)]

    for arch, shape in cells:
        cid = cell_id(arch, shape, args.multi_pod)
        path = os.path.join(args.out, cid + ".json")
        if args.skip_existing and os.path.exists(path):
            with open(path) as f:
                if json.load(f).get("status") in ("ok", "skipped"):
                    print(f"[skip] {cid}")
                    continue
        t0 = time.time()
        r = run_cell(arch, shape, args.multi_pod, out_dir=args.out,
                     grad_accum=args.grad_accum,
                     calibrate_costs=not args.multi_pod, sp=args.sp,
                     remat_policy=args.remat_policy)
        status = r["status"]
        extra = "" if status != "error" else " :: " + r["error"][:160]
        print(f"[{status}] {cid} ({time.time()-t0:.1f}s){extra}",
              flush=True)


if __name__ == "__main__":
    main()
