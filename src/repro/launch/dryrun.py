"""Multi-pod dry-run: lower + compile every (architecture × input shape ×
mesh) combination on placeholder devices, and extract roofline terms.

Run:
    PYTHONPATH=src python -m repro.launch.dryrun [--arch all] [--shape all]
        [--mesh both] [--moe dense|capacity] [--out experiments/dryrun]

This file — and ONLY this file — forces 512 host platform devices; smoke
tests and benchmarks see the real device count.
"""
# The XLA_FLAGS assignment MUST precede every other import (jax locks the
# device count on first initialization).
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, applicable_shapes, get_arch, get_shape
from repro.distributed import (
    batch_spec,
    opt_state_specs,
    param_specs,
    sanitize_tree,
    to_named,
)
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.models import build_model
from repro.models.layers import abstract as abstract_params_of
from repro.optim import AdamW
from repro.roofline import Roofline, model_flops_estimate, parse_collectives


def build_train_step(model, optimizer, microbatch: int = 1):
    """Train step, optionally with gradient accumulation over ``microbatch``
    slices of the global batch (sequential ``lax.scan`` — the deployment
    answer to the §Dry-run finding that batch-256×4k training exceeds one
    v5e's HBM for the larger architectures)."""

    def train_step(params, opt_state, batch):
        if microbatch <= 1:
            loss, grads = jax.value_and_grad(
                lambda p: model.loss(p, batch))(params)
        else:
            def slice_mb(i, x):
                mb = x.shape[0] // microbatch
                return jax.lax.dynamic_slice_in_dim(x, i * mb, mb, 0)

            def acc_step(carry, i):
                loss_acc, grad_acc = carry
                mb_batch = {k: slice_mb(i, v) for k, v in batch.items()}
                loss, grads = jax.value_and_grad(
                    lambda p: model.loss(p, mb_batch))(params)
                grad_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) / microbatch,
                    grad_acc, grads)
                return (loss_acc + loss / microbatch, grad_acc), None

            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(
                acc_step, (jnp.zeros((), jnp.float32), zero),
                jnp.arange(microbatch))
            grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads,
                                 params)
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        return new_params, new_opt, loss

    return train_step


def _block_abstract(defs_blocks, mesh):
    """Abstract single-block params (strip the stacked n_blocks axis)."""
    import dataclasses
    from repro.models.layers import ParamDef

    def strip(d: ParamDef) -> ParamDef:
        return dataclasses.replace(d, shape=d.shape[1:], spec=P(*list(d.spec)[1:]))

    defs1 = jax.tree.map(strip, defs_blocks,
                         is_leaf=lambda x: isinstance(x, ParamDef))
    return (abstract_params_of(defs1), to_named(param_specs(defs1, mesh), mesh))


def _analyze(compiled, n_dev):
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    coll = parse_collectives(compiled.as_text())
    return flops, bytes_accessed, coll


def _block_cost(model, mesh, dp, x_shape, *, kind, memory_shape=None,
                cache_block=None, cache_specs_block=None):
    """Lower+compile one pattern-block (and its VJP for training) so that
    scan-body costs can be scaled by the trip count — XLA cost_analysis
    counts a while-loop body exactly once regardless of iterations."""
    from repro.models import transformer as T
    cfg = model.cfg
    defs_blocks = model.defs["blocks"]
    abs_p, sh_p = _block_abstract(defs_blocks, mesh)
    x = jax.ShapeDtypeStruct(x_shape, jnp.bfloat16)
    x_sh = NamedSharding(mesh, batch_spec(x_shape, mesh, dp))
    mem_args, mem_sh = (), ()
    if memory_shape is not None:
        mem_args = (jax.ShapeDtypeStruct(memory_shape, jnp.bfloat16),)
        mem_sh = (NamedSharding(mesh, batch_spec(memory_shape, mesh, dp)),)

    if kind == "decode":
        def fn(p_blocks, xx, cache, pos):
            new_c = []
            for i, spec in enumerate(cfg.layer_pattern):
                xx, nc = T.apply_block_decode(
                    cfg, spec, p_blocks[i], xx, cache[i], pos,
                    long_serving=model.long_serving)
                new_c.append(nc)
            return xx, tuple(new_c)

        pos = jax.ShapeDtypeStruct((), jnp.int32)
        jitted = jax.jit(fn, in_shardings=(
            sh_p, x_sh, cache_specs_block, NamedSharding(mesh, P())))
        lowered = jitted.lower(abs_p, x, cache_block, pos)
        return lowered.compile()

    per_layer_ck = len(cfg.layer_pattern) > 4   # mirror Model.forward

    def fwd(p_blocks, xx, *mem):
        memory = mem[0] if mem else None
        aux = jnp.zeros((), jnp.float32)
        for i, spec in enumerate(cfg.layer_pattern):
            def f(p, x, spec=spec):
                return T.apply_block(cfg, spec, p, x, memory=memory,
                                     moe_strategy=model.moe_strategy,
                                     long_serving=model.long_serving)
            if per_layer_ck:
                f = jax.checkpoint(f)
            xx, a = f(p_blocks[i], xx)
            aux = aux + a
        return xx, aux

    if kind == "train":
        ck = fwd if per_layer_ck else jax.checkpoint(fwd)

        def fn(p_blocks, xx, ybar, *mem):
            (y, aux), vjp = jax.vjp(lambda pp, xi: ck(pp, xi, *mem),
                                    p_blocks, xx)
            return vjp((ybar, jnp.ones((), jnp.float32)))

        jitted = jax.jit(fn, in_shardings=(sh_p, x_sh, x_sh) + mem_sh)
        lowered = jitted.lower(abs_p, x, x, *mem_args)
    else:  # prefill forward only
        jitted = jax.jit(fwd, in_shardings=(sh_p, x_sh) + mem_sh)
        lowered = jitted.lower(abs_p, x, *mem_args)
    return lowered.compile()


def _encoder_cost(model, mesh, dp, frames_shape, *, kind):
    """Single encoder layer cost (enc-dec models), same methodology."""
    from repro.models import layers as Lmod
    from repro.models.layers import rms_norm as _rms
    from repro.models import attention as attn_mod
    cfg, enc = model.cfg, model.cfg.encoder
    abs_p, sh_p = _block_abstract(model.defs["encoder"]["layers"], mesh)
    x = jax.ShapeDtypeStruct(frames_shape, jnp.bfloat16)
    x_sh = NamedSharding(mesh, batch_spec(frames_shape, mesh, dp))

    def fwd(p, xx):
        h = _rms(xx, p["attn_norm"], cfg.norm_eps)
        xx = xx + attn_mod.attn_apply(p["attn"], h, cfg=cfg, causal=False,
                                      window=0, n_heads=enc.n_heads,
                                      n_kv=enc.n_kv_heads,
                                      head_dim=enc.head_dim)
        h = _rms(xx, p["mlp_norm"], cfg.norm_eps)
        return xx + Lmod.mlp_apply(p["mlp"], h, cfg.mlp_activation)

    if kind == "train":
        ck = jax.checkpoint(fwd)

        def fn(p, xx, ybar):
            y, vjp = jax.vjp(ck, p, xx)
            return vjp(ybar)

        jitted = jax.jit(fn, in_shardings=(sh_p, x_sh, x_sh))
        lowered = jitted.lower(abs_p, x, x)
    else:
        jitted = jax.jit(fwd, in_shardings=(sh_p, x_sh))
        lowered = jitted.lower(abs_p, x)
    return lowered.compile()


def dryrun_one(arch_name: str, shape_name: str, mesh: Mesh, mesh_name: str,
               *, moe_strategy: str = "dense", zero1: bool = True,
               sharding: str = "tp", norm_mult_fp32: bool = True,
               force_blockwise: bool = False, ce_upcast: bool = True,
               microbatch: int = 1, tag: str = "",
               out_dir: Optional[str] = None, model_kwargs: Optional[dict] = None,
               verbose: bool = True) -> Roofline:
    import dataclasses as _dc
    from repro.models import attention as _attn_mod
    from repro.models import layers as _layers_mod
    from repro.models.layers import ParamDef as _PD
    _layers_mod.NORM_MULT_FP32 = norm_mult_fp32
    _attn_mod.FORCE_BLOCKWISE = force_blockwise
    from repro.models import model_zoo as _mz_mod
    _mz_mod.CE_UPCAST = ce_upcast

    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    model_shards = mesh.shape["model"] if sharding == "tp" else 1
    dp = dp_axes(mesh) if sharding == "tp" else tuple(mesh.axis_names)
    n_dev = int(np.prod(list(mesh.shape.values())))

    kw = dict(scan_unroll=1)
    kw.update(model_kwargs or {})
    model = build_model(
        cfg, model_shards=model_shards, dtype=jnp.bfloat16,
        moe_strategy=moe_strategy,
        long_serving=(shape_name == "long_500k"),
        **kw)
    defs = model.defs
    if sharding == "dp":
        # pure data parallelism (paper-faithful Horovod-style): params
        # replicated; the whole mesh is one big data axis; opt state ZeRO-1
        # sharded over it.
        defs = jax.tree.map(lambda d: _dc.replace(d, spec=P()), defs,
                            is_leaf=lambda x: isinstance(x, _PD))
        model.__dict__["defs"] = defs
    abstract_params = abstract_params_of(defs)
    p_specs = param_specs(defs, mesh)
    p_sh = to_named(p_specs, mesh)

    batch = model.input_specs(shape)
    t0 = time.time()

    if shape.kind == "train":
        optimizer = AdamW()
        abstract_opt = jax.eval_shape(optimizer.init, abstract_params)
        o_specs = opt_state_specs(defs, mesh, dp, zero1=zero1)
        o_sh = type(abstract_opt)(
            step=NamedSharding(mesh, P()),
            mu=to_named(o_specs, mesh), nu=to_named(o_specs, mesh))
        b_sh = {k: NamedSharding(mesh, batch_spec(v.shape, mesh, dp))
                for k, v in batch.items()}
        fn = build_train_step(model, optimizer, microbatch=microbatch)
        jitted = jax.jit(fn, in_shardings=(p_sh, o_sh, b_sh),
                         out_shardings=(p_sh, o_sh, NamedSharding(mesh, P())))
        lowered = jitted.lower(abstract_params, abstract_opt, batch)
        tokens = shape.global_batch * shape.seq_len
        kind = "train"
    elif shape.kind == "prefill":
        b_sh = {k: NamedSharding(mesh, batch_spec(v.shape, mesh, dp))
                for k, v in batch.items()}
        fn = lambda params, b: model.prefill(params, b)
        jitted = jax.jit(fn, in_shardings=(p_sh, b_sh))
        lowered = jitted.lower(abstract_params, batch)
        tokens = shape.global_batch * shape.seq_len
        kind = "prefill"
    else:  # decode
        cache = batch["cache"]
        c_specs = model.cache_specs(dp if len(dp) > 1 else dp[0], "model")
        c_specs = sanitize_tree(cache, c_specs, mesh)
        c_sh = to_named(c_specs, mesh)
        tok_sh = NamedSharding(mesh, batch_spec(batch["tokens"].shape, mesh, dp))
        fn = lambda params, cache, toks, pos: model.decode_step(
            params, cache, toks, pos)
        jitted = jax.jit(
            fn, in_shardings=(p_sh, c_sh, tok_sh, NamedSharding(mesh, P())),
            out_shardings=(None, c_sh))
        lowered = jitted.lower(abstract_params, cache, batch["tokens"],
                               batch["pos"])
        tokens = shape.global_batch
        kind = "decode"

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    flops, bytes_accessed, coll = _analyze(compiled, n_dev)
    try:
        mem = compiled.memory_analysis()
        bytes_per_device = getattr(mem, "temp_size_in_bytes", None)
        if bytes_per_device is not None:
            bytes_per_device += getattr(mem, "argument_size_in_bytes", 0)
    except Exception:
        bytes_per_device = None

    # ---- scan-body cost correction (see _block_cost docstring) ----
    n_extra = cfg.n_blocks - 1
    if n_extra > 0:
        d_model = cfg.d_model
        if shape.kind == "train":
            bsz = shape.global_batch
            seq = shape.seq_len if cfg.frontend != "vision" else shape.seq_len
            x_shape = (bsz, seq, d_model)
            mem_shape = ((bsz, shape.seq_len // 4, cfg.encoder.d_model)
                         if cfg.is_encdec else None)
            blk = _block_cost(model, mesh, dp, x_shape, kind="train",
                              memory_shape=mem_shape)
        elif shape.kind == "prefill":
            x_shape = (shape.global_batch, shape.seq_len, d_model)
            mem_shape = ((shape.global_batch, shape.seq_len // 4,
                          cfg.encoder.d_model) if cfg.is_encdec else None)
            blk = _block_cost(model, mesh, dp, x_shape, kind="prefill",
                              memory_shape=mem_shape)
        else:
            import dataclasses as _dc
            cache_block = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), cache)
            cs_block = jax.tree.map(
                lambda sh: NamedSharding(mesh, P(*list(sh.spec)[1:])), c_sh,
                is_leaf=lambda x: isinstance(x, NamedSharding))
            blk = _block_cost(model, mesh, dp,
                              (shape.global_batch, 1, d_model),
                              kind="decode", cache_block=cache_block,
                              cache_specs_block=cs_block)
        bf, bb, bc = _analyze(blk, n_dev)
        flops += n_extra * bf
        bytes_accessed += n_extra * bb
        coll.link_bytes += n_extra * bc.link_bytes
        for k2, v2 in bc.counts.items():
            coll.counts[k2] = coll.counts.get(k2, 0) + n_extra * v2
        for k2, v2 in bc.bytes_by_kind.items():
            coll.bytes_by_kind[k2] = (coll.bytes_by_kind.get(k2, 0)
                                      + n_extra * v2)
    if cfg.is_encdec and shape.kind != "decode" and cfg.encoder.n_layers > 1:
        enc_extra = cfg.encoder.n_layers - 1
        frames_shape = (shape.global_batch, shape.seq_len // 4,
                        cfg.encoder.d_model)
        eb = _encoder_cost(model, mesh, dp, frames_shape,
                           kind=shape.kind)
        ef, ebts, ec = _analyze(eb, n_dev)
        flops += enc_extra * ef
        bytes_accessed += enc_extra * ebts
        coll.link_bytes += enc_extra * ec.link_bytes

    # On the host backend cost_analysis reports per-program totals of the
    # partitioned module (per-device); scale to the full job.
    n_params = model.n_params()
    n_active = model.n_active_params()
    r = Roofline(
        arch=arch_name, shape=shape_name, mesh=mesh_name, n_devices=n_dev,
        hlo_flops=flops * n_dev, hlo_bytes=bytes_accessed * n_dev,
        collective_link_bytes=coll.link_bytes,
        model_flops=model_flops_estimate(n_params, n_active, tokens, kind),
        n_params=n_params, n_active_params=n_active,
        bytes_per_device=bytes_per_device,
        collective_counts=coll.counts, collective_bytes=coll.bytes_by_kind)

    if verbose:
        print(f"[dryrun] {arch_name:24s} {shape_name:12s} {mesh_name:6s} "
              f"lower {t_lower:6.1f}s compile {t_compile:6.1f}s  "
              f"flops/dev {flops:.3e}  coll {coll.link_bytes/1e6:8.1f}MB  "
              f"bottleneck={r.bottleneck}", flush=True)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = f"{arch_name}__{shape_name}__{mesh_name}"
        if tag:
            fname += f"__{tag}"
        extra = dict(t_lower_s=t_lower, t_compile_s=t_compile,
                     moe_strategy=moe_strategy, zero1=zero1,
                     sharding=sharding, norm_mult_fp32=norm_mult_fp32,
                     force_blockwise=force_blockwise, ce_upcast=ce_upcast,
                     tag=tag)
        with open(os.path.join(out_dir, fname + ".json"), "w") as f:
            json.dump({**r.to_json(), **extra}, f, indent=1)
    return r


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--moe", default="dense", choices=["dense", "capacity"])
    ap.add_argument("--no-zero1", action="store_true",
                    help="paper-faithful plain DP (opt state replicated "
                         "over data axes)")
    ap.add_argument("--sharding", default="tp", choices=["tp", "dp"])
    ap.add_argument("--norm-bf16", action="store_true",
                    help="norm multiplies in bf16 (fp32 stats only)")
    ap.add_argument("--flash", action="store_true",
                    help="force blockwise (flash) attention at all lengths")
    ap.add_argument("--ce-bf16", action="store_true",
                    help="mixed-precision CE loss (no fp32 logits copy)")
    ap.add_argument("--microbatch", type=int, default=1,
                    help="gradient-accumulation slices of the global batch")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else args.arch.split(",")
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("1pod-16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("2pod-2x16x16", make_production_mesh(multi_pod=True)))

    results, failures = [], []
    for arch_name in archs:
        cfg = get_arch(arch_name)
        shapes = (applicable_shapes(cfg) if args.shape == "all"
                  else args.shape.split(","))
        for shape_name in shapes:
            if shape_name not in applicable_shapes(cfg):
                print(f"[skip] {arch_name} x {shape_name}: see DESIGN.md "
                      f"long-context table")
                continue
            for mesh_name, mesh in meshes:
                try:
                    results.append(dryrun_one(
                        arch_name, shape_name, mesh, mesh_name,
                        moe_strategy=args.moe, zero1=not args.no_zero1,
                        sharding=args.sharding,
                        norm_mult_fp32=not args.norm_bf16,
                        force_blockwise=args.flash,
                        ce_upcast=not args.ce_bf16,
                        microbatch=args.microbatch, tag=args.tag,
                        out_dir=args.out))
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    failures.append((arch_name, shape_name, mesh_name,
                                     repr(e)))
    print(f"\n{len(results)} combination(s) compiled, "
          f"{len(failures)} failure(s)")
    for f in failures:
        print("FAIL:", *f)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
