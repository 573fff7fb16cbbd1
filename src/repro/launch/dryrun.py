import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ruff: noqa: E402  (the two lines above MUST precede any jax import —
# jax locks the device count at first initialisation)
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the real step function (train_step for
train_4k, prefill for prefill_32k, serve_step for decode shapes) with
full production shardings, lowers it against ShapeDtypeStructs (zero
allocation), compiles it, prints memory/cost analysis, and writes the
roofline terms to ``experiments/dryrun/<arch>_<shape>_<mesh>.json``.

Usage:
    python -m repro.launch.dryrun --arch smollm-360m --shape train_4k
    python -m repro.launch.dryrun --all --mesh both
    python -m repro.launch.dryrun --all --mesh single --opt act_seq_shard=0
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compile_cache import enable_compile_cache
from repro.configs import SHAPES, cells, get_config, input_specs, shape_skip_reason
from repro.launch.mesh import batch_axes, make_production_mesh
from repro.models import abstract_params, build_model
from repro.models.params import partition_specs
from repro.roofline.analysis import analyze
from repro.serve import make_serve_step
from repro.train import TrainConfig, make_train_step

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _axis_size(mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def sanitize_specs(mesh, specs, abstracts):
    """Drop sharding on any dim the mesh axis doesn't divide.

    jit rejects non-divisible shardings on *arguments* (e.g. vocab 51865
    on a 16-way axis, 5 kv heads on 16-way TP). Production frameworks pad
    such dims; the baseline replicates them instead (vocab padding is a
    §Perf item). Logs nothing — the dry-run JSON records final specs.
    """
    def fix(spec, sds):
        parts = list(spec) + [None] * (sds.ndim - len(spec))
        out = []
        for dim, axis in zip(sds.shape, parts):
            out.append(axis if dim % _axis_size(mesh, axis) == 0 else None)
        return P(*out)

    return jax.tree.map(fix, specs, abstracts,
                        is_leaf=lambda x: isinstance(x, P))


def _batch_specs(specs_tree, baxes):
    """P(batch_axes, None, ...) for every array input; scalars replicated."""
    def one(sds):
        if sds.ndim == 0:
            return P()
        return P(baxes, *([None] * (sds.ndim - 1)))
    return jax.tree.map(one, specs_tree)


DEFAULT_OPTS = {
    "act_seq_shard": 1,     # Megatron-SP residual sharding for train/prefill
    "remat": "1",   # "1" | "0" | "dots"
    "donate": 1,
    "microbatches": 1,
    "window_cache": 0,      # gemma3: truncate local-layer KV cache to window
    "score_shard": 1,       # decode: pin scores to the cache's seq sharding
    "flash": 0,             # Pallas attention kernel path (TPU deploy)
    "device_order": "hilbert",
}


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str,
             opts: dict) -> dict:
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    baxes = batch_axes(mesh)
    n_dev = mesh.devices.size

    if shape.mode in ("train", "prefill") and opts["act_seq_shard"]:
        cfg = dataclasses.replace(cfg, act_spec=(baxes, "model", None))
    if cfg.family == "moe":
        cfg = dataclasses.replace(cfg, ep_axis="model")
    if opts["flash"]:
        cfg = dataclasses.replace(cfg, use_flash_kernel=True)
    model = build_model(cfg)

    t0 = time.time()
    if shape.mode == "train":
        params_abs = model.abstract(jnp.float32)
        pspecs = sanitize_specs(mesh, model.specs(), params_abs)
        opt_abs = {"m": params_abs, "v": params_abs,
                   "step": jax.ShapeDtypeStruct((), jnp.int32)}
        opt_specs = {"m": pspecs, "v": pspecs, "step": P()}
        batch_abs = input_specs(cfg, shape)
        bspecs = _batch_specs(batch_abs, baxes)
        rm = opts["remat"]
        rm = {"1": True, "0": False, 1: True, 0: False}.get(rm, rm)
        step = make_train_step(model, TrainConfig(
            microbatches=opts["microbatches"], remat=rm))
        in_sh = (_ns(mesh, pspecs), _ns(mesh, opt_specs), _ns(mesh, bspecs))
        out_sh = (_ns(mesh, pspecs), _ns(mesh, opt_specs),
                  _ns(mesh, jax.tree.map(lambda _: P(),
                                         {"loss": 0, "grad_norm": 0, "lr": 0})))
        donate = (0, 1) if opts["donate"] else ()
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        with mesh:
            lowered = jitted.lower(params_abs, opt_abs, batch_abs)
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * model.n_active_params() * tokens
    elif shape.mode == "prefill":
        params_abs = model.abstract(jnp.bfloat16)
        pspecs = sanitize_specs(mesh, model.specs(), params_abs)
        batch_abs = input_specs(cfg, shape)
        bspecs = _batch_specs(batch_abs, baxes)

        def step(params, batch):
            return model.prefill(params, batch)

        in_sh = (_ns(mesh, pspecs), _ns(mesh, bspecs))
        vocab_rule = ("model" if cfg.vocab_padded % mesh.shape["model"] == 0
                      else None)
        jitted = jax.jit(step, in_shardings=in_sh,
                         out_shardings=NamedSharding(mesh, P(baxes, vocab_rule)))
        with mesh:
            lowered = jitted.lower(params_abs, batch_abs)
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * model.n_active_params() * tokens
    else:  # decode
        params_abs = model.abstract(jnp.bfloat16)
        pspecs = sanitize_specs(mesh, model.specs(), params_abs)
        B, S = shape.global_batch, shape.seq_len
        cache_abs = model.abstract_cache(B, S, jnp.bfloat16)
        b_rule = baxes if B >= 8 else None
        # sequence-parallel decode cache: KV-head counts (1..8) don't
        # divide the 16-way TP axis, the 2^k sequence always does; B=1
        # (long_500k) additionally spreads seq over the batch axes.
        seq_rule = ("data", "model") if B == 1 else "model"
        if opts["score_shard"]:
            cfg = dataclasses.replace(
                cfg, score_spec=(b_rule, None, None, seq_rule))
            model = build_model(cfg)
        cache_specs = model.cache_specs(
            B, S, extra_rules={"batch": b_rule, "seq": seq_rule,
                               "kv_heads": None, "heads": None})
        cache_specs = sanitize_specs(mesh, cache_specs, cache_abs)
        batch_abs = input_specs(cfg, shape)
        bspecs = _batch_specs(batch_abs, b_rule)
        step = make_serve_step(model)
        in_sh = (_ns(mesh, pspecs), _ns(mesh, cache_specs), _ns(mesh, bspecs))
        out_sh = (NamedSharding(mesh, P(b_rule)), _ns(mesh, cache_specs))
        donate = (1,) if opts["donate"] else ()
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        with mesh:
            lowered = jitted.lower(params_abs, cache_abs, batch_abs)
        model_flops = 2.0 * model.n_active_params() * B
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cell = analyze(arch, shape_name, mesh_name, n_dev, compiled, model_flops)
    rec = cell.to_dict()
    rec.update(t_lower_s=t_lower, t_compile_s=t_compile, opts=dict(opts),
               n_params=model.n_params(), n_active=model.n_active_params())
    print(f"  memory_analysis: {mem}")
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    print(f"  cost_analysis: flops={ca.get('flops', 0):.3e} "
          f"bytes={ca.get('bytes accessed', 0):.3e}")
    print(f"  roofline: compute {cell.t_compute*1e3:.2f} ms | memory "
          f"{cell.t_memory*1e3:.2f} ms | collective "
          f"{cell.t_collective*1e3:.2f} ms -> {cell.bottleneck}-bound, "
          f"useful-flops {cell.useful_flops_frac:.2f}, "
          f"MFU-bound {cell.mfu_bound:.2%}")
    return rec


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--tag", default="")
    ap.add_argument("--opt", action="append", default=[],
                    help="key=val overrides, e.g. --opt act_seq_shard=0")
    args = ap.parse_args()

    opts = dict(DEFAULT_OPTS)
    for kv in args.opt:
        k, v = kv.split("=", 1)
        opts[k] = type(DEFAULT_OPTS.get(k, ""))(v) if k in DEFAULT_OPTS else v

    if args.all:
        todo = cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        reason = shape_skip_reason(args.arch, args.shape)
        if reason:
            print(f"SKIP {args.arch} × {args.shape}: {reason}")
            return
        todo = [(args.arch, args.shape)]

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16",
                       make_production_mesh(multi_pod=False,
                                            device_order=opts["device_order"])))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16",
                       make_production_mesh(multi_pod=True,
                                            device_order=opts["device_order"])))

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for mesh_name, mesh in meshes:
        for arch, shape_name in todo:
            key = f"{arch}_{shape_name}_{mesh_name}{args.tag}"
            print(f"[dryrun] {key}")
            try:
                rec = run_cell(arch, shape_name, mesh, mesh_name, opts)
                with open(os.path.join(args.out, key + ".json"), "w") as f:
                    json.dump(rec, f, indent=1)
            except Exception as e:  # noqa: BLE001 — report-and-continue runner
                traceback.print_exc()
                failures.append((key, repr(e)))
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES:")
        for k, e in failures:
            print("  ", k, e)
        raise SystemExit(1)
    print(f"[dryrun] all {len(todo) * len(meshes)} cells compiled OK")


if __name__ == "__main__":
    main()
