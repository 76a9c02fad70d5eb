import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST be the first two lines: jax locks the device count on first init.
# The dry-run (and only the dry-run) needs 512 placeholder host devices.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the production mesh, attaches NamedShardings to
every input's ShapeDtypeStruct (params, optimizer state, batch / KV cache),
lowers the real train_step / prefill / decode_step, compiles it, and records
``memory_analysis()`` + ``cost_analysis()`` + the collective schedule for
EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--impl distr|xla_flash]
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax

import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_NAMES, SHAPES, get_config, input_specs
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.roofline import analysis as roof
from repro.serve import kv_cache
from repro.serve.serve_step import make_decode_step, make_prefill
from repro.train.optimizer import OptimizerConfig, adamw_init
from repro.train.train_step import make_train_step
from repro.utils import tree_bytes

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")


def tpu_memory_estimate(cfg, shape, mesh, p_shapes) -> dict:
    """Analytic per-chip HBM estimate for the real TPU target.

    The CPU dry-run backend float-normalises bf16 → f32 (its 'wide.*'
    computations), inflating ``memory_analysis`` temps by up to 2× on bf16
    models; this estimate is the TPU-side budget check reported next to it.
    """
    devs = int(mesh.size)
    model_par = int(mesh.shape.get("model", 1))
    dp = devs // model_par
    param_b = tree_bytes(p_shapes)  # fp32 master params
    out = {"params": param_b / devs}
    if shape.kind == "train":
        out["opt_state"] = 2 * param_b / devs  # adam m+v fp32
        tokens = shape.global_batch * shape.seq_len
        # per-layer bf16 residual carry, sequence-sharded over model
        out["saved_carries"] = cfg.n_layers * tokens * cfg.d_model * 2 / devs
        # logits + softmax grads (bf16 fwd + f32 bwd ≈ 6 B/elem)
        out["logits"] = tokens / dp * cfg.padded_vocab / model_par * 6
        out["transient"] = 2 * 2**30  # block-level working set, bounded
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        out["activations"] = 4 * tokens * cfg.d_model * 2 / devs
        out["kv_cache"] = (
            2 * cfg.n_layers * tokens * cfg.n_kv_heads * cfg.head_dim_ * 2 / devs
            if not cfg.is_attention_free
            else 0
        )
        out["transient"] = 2 * 2**30
    else:  # decode
        from repro.serve import kv_cache as kvc

        cache_b = tree_bytes(kvc.cache_struct(cfg, shape.global_batch, shape.seq_len))
        out["kv_cache"] = cache_b / devs
        out["transient"] = 1 * 2**30
    out["total"] = sum(out.values())
    return {k: int(v) for k, v in out.items()}


def _struct_with(shapes_tree, shardings_tree):
    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes_tree,
        shardings_tree,
    )


def _batch_struct(specs: dict, mesh):
    shard = shd.batch_shardings(specs, mesh)
    return {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=shard[k])
        for k, v in specs.items()
    }


def build_lowered(arch: str, shape_name: str, *, multi_pod: bool,
                  impl: str | None = None, overrides: dict | None = None):
    """→ (lowered, meta) for one cell; raises on skip."""
    cfg = get_config(arch)
    if impl:
        cfg = cfg.replace(attention=cfg.attention.with_impl(impl))
    if overrides:
        overrides = dict(overrides)
        if overrides.pop("distr_decode", False):
            cfg = cfg.replace(
                attention=dataclasses.replace(cfg.attention, distr_decode=True)
            )
        if overrides:
            cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    reason = cfg.skip_reason(shape)
    if reason:
        raise SkipCell(reason)

    mesh = make_production_mesh(multi_pod=multi_pod)
    key = jax.random.PRNGKey(0)
    p_shapes = jax.eval_shape(lambda k: lm.init_params(k, cfg), key)
    axes = lm.param_axes(cfg)
    p_shard = shd.param_shardings(axes, p_shapes, mesh, fsdp=cfg.fsdp)
    p_struct = _struct_with(p_shapes, p_shard)
    total, active = roof.active_params(cfg, p_shapes)

    meta = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "pod2x16x16" if multi_pod else "16x16",
        "devices": int(mesh.size),
        "impl": cfg.attention.impl,
        "total_params": total,
        "active_params": active,
        "model_flops": roof.model_flops(cfg, shape, active),
        "tpu_memory_estimate": tpu_memory_estimate(cfg, shape, mesh, p_shapes),
    }

    with jax.sharding.set_mesh(mesh):
        if shape.kind == "train":
            o_shapes = jax.eval_shape(adamw_init, p_shapes)
            o_shard = {
                "m": p_shard,
                "v": p_shard,
                "count": shd.replicated(mesh),
            }
            o_struct = _struct_with(o_shapes, o_shard)
            batch = _batch_struct(input_specs(cfg, shape), mesh)
            step_struct = jax.ShapeDtypeStruct((), jnp.int32,
                                               sharding=shd.replicated(mesh))
            opt_cfg = OptimizerConfig(total_steps=10_000)
            fn = make_train_step(cfg, opt_cfg)
            lowered = jax.jit(
                fn, donate_argnums=(0, 1),
                out_shardings=(p_shard, o_shard, None),
            ).lower(p_struct, o_struct, batch, step_struct)
        elif shape.kind == "prefill":
            batch = _batch_struct(input_specs(cfg, shape), mesh)
            fn = make_prefill(cfg, shape.seq_len)
            kwargs = {k: v for k, v in batch.items() if k != "tokens"}
            lowered = jax.jit(fn).lower(p_struct, batch["tokens"], **kwargs)
        else:  # decode
            b = shape.global_batch
            cache_shapes = kv_cache.cache_struct(cfg, b, shape.seq_len)
            cache_pspec = kv_cache.cache_pspecs(
                cfg, mesh, batch=b, max_len=shape.seq_len
            )
            cache_shard = {
                k: NamedSharding(mesh, cache_pspec[k]) for k in cache_shapes
            }
            cache_struct_in = _struct_with(cache_shapes, cache_shard)
            dp = shd.dp_axes_for(mesh, b)
            tok = jax.ShapeDtypeStruct(
                (b, 1), jnp.int32, sharding=NamedSharding(mesh, P(dp, None))
            )
            pos = jax.ShapeDtypeStruct(
                (b,), jnp.int32, sharding=NamedSharding(mesh, P(dp))
            )
            fn = make_decode_step(cfg)
            lowered = jax.jit(
                fn, donate_argnums=(2,), out_shardings=(None, cache_shard)
            ).lower(p_struct, tok, cache_struct_in, pos)
    return lowered, meta


class SkipCell(Exception):
    pass


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             impl: str | None = None, save: bool = True,
             tag: str = "", overrides: dict | None = None) -> dict:
    t0 = time.time()
    try:
        lowered, meta = build_lowered(
            arch, shape_name, multi_pod=multi_pod, impl=impl,
            overrides=overrides,
        )
    except SkipCell as e:
        rec = {"arch": arch, "shape": shape_name,
               "mesh": "pod2x16x16" if multi_pod else "16x16",
               "status": "skipped", "reason": str(e)}
        print(f"[dryrun] SKIP {arch} × {shape_name}: {e}")
        if save:
            _save(rec, tag)
        return rec

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    terms = roof.roofline(compiled)
    rec = {
        **meta,
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            # peak live ≈ args + temps − donated aliases (per device)
            "per_device_total": int(
                mem.argument_size_in_bytes
                + mem.temp_size_in_bytes
                + mem.output_size_in_bytes
                - mem.alias_size_in_bytes
            ),
        },
        "roofline": terms.as_dict(),
        "useful_flops_ratio": (
            meta["model_flops"] / meta["devices"] / terms.flops_per_dev
            if terms.flops_per_dev
            else None
        ),
    }
    print(
        f"[dryrun] OK {arch} × {shape_name} × {rec['mesh']} "
        f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)\n"
        f"  mem/device: {rec['memory']['per_device_total']/2**30:.2f} GiB "
        f"(args {mem.argument_size_in_bytes/2**30:.2f} + temp "
        f"{mem.temp_size_in_bytes/2**30:.2f} GiB; TPU est "
        f"{meta['tpu_memory_estimate']['total']/2**30:.2f} GiB)\n"
        f"  roofline: compute {terms.compute_s*1e3:.2f} ms | memory "
        f"{terms.memory_s*1e3:.2f} ms | collective {terms.collective_s*1e3:.2f} ms "
        f"→ {terms.dominant}-bound; useful-FLOPs ratio "
        f"{rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'], 3)}"
    )
    if save:
        _save(rec, tag)
    return rec


def _save(rec: dict, tag: str = "") -> None:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    name = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}"
    if rec.get("impl") and rec["impl"] != "distr":
        name += f"_{rec['impl']}"
    if tag:
        name += f"_{tag}"
    with open(os.path.join(RESULTS_DIR, name + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--impl", default=None,
                    help="attention impl override (e.g. xla_flash baseline)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg override key=value (e.g. attn_shard=heads)")
    args = ap.parse_args()
    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        if v in ("True", "False"):
            v = v == "True"
        elif v.isdigit():
            v = int(v)
        overrides[k] = v

    cells = []
    if args.all:
        for a in ARCH_NAMES:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            try:
                run_cell(arch, shape, multi_pod=mp, impl=args.impl,
                         tag=args.tag, overrides=overrides or None)
            except Exception as e:  # noqa: BLE001 — report and continue
                failures.append((arch, shape, mp, repr(e)))
                print(f"[dryrun] FAIL {arch} × {shape} multi_pod={mp}: {e}")
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run cells failed: {failures}")


if __name__ == "__main__":
    main()
