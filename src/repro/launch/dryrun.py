import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

The two lines above run before ANY other import (jax locks the device count
at first init): the dry-run — and only the dry-run — sees 512 placeholder
host devices so ``jax.make_mesh`` can build the production meshes.

For each combination this lowers the right entry point (train_step for
train_4k, prefill for prefill_32k, serve/decode_step for decode shapes) with
ShapeDtypeStruct stand-ins (zero allocation), compiles under the mesh,
prints ``memory_analysis()`` / ``cost_analysis()``, extracts the roofline
terms, and appends everything to a JSON results file consumed by
EXPERIMENTS.md and ``benchmarks/``.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
        --mesh both --out results/dryrun.json
"""

import argparse
import json
import traceback
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import (
    ARCH_IDS,
    INPUT_SHAPES,
    InputShape,
    ModelConfig,
    get_config,
    supports_shape,
)
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import logical_to_pspec, make_rules, sharding_rules
from repro.models.layers import Axes, is_axes
from repro.models.model import Model
from repro.obs.clock import clock
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.optim.schedule import linear_warmup_cosine
from repro.roofline import HW_V5E, collective_bytes_from_hlo, roofline_from_compiled

from jax.sharding import NamedSharding, PartitionSpec as P

DRYRUN_ARCHS = tuple(a for a in ARCH_IDS if a != "openvla-7b")

# per-kind logical->mesh overrides (DESIGN.md §5)
RULE_OVERRIDES = {
    "train": {"embed": ("data",), "act_seq": ("model",), "kv_seq": ()},
    "prefill": {"embed": (), "act_seq": ("model",), "kv_seq": ()},
    "decode": {"embed": (), "act_seq": (), "kv_seq": ("model",)},
}


def _shardings_for(tree_sds, tree_logical, mesh, rules):
    """NamedShardings for an SDS tree from an Axes tree (divisibility-guarded)."""

    return jax.tree.map(
        lambda ax, sds: NamedSharding(
            mesh, logical_to_pspec(sds.shape, ax.names, mesh, rules)
        ),
        tree_logical,
        tree_sds,
        is_leaf=is_axes,
    )


def _with_shardings(tree_sds, tree_shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree_sds,
        tree_shardings,
    )


def input_specs(cfg: ModelConfig, shape: InputShape, mesh, rules) -> Dict:
    """ShapeDtypeStruct stand-ins for every model input of this shape."""

    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    batch_spec = lambda shp, logical, dtype=i32: jax.ShapeDtypeStruct(
        shp, dtype, sharding=NamedSharding(mesh, logical_to_pspec(shp, logical, mesh, rules))
    )
    out: Dict = {}
    is_mm = cfg.modality in ("vision", "audio") and not cfg.encoder_decoder
    s_text = s - (cfg.num_modality_tokens if is_mm else 0)
    if shape.kind in ("train", "prefill"):
        out["tokens"] = batch_spec((b, s_text), ("batch", None))
        if is_mm:
            out["frontend"] = batch_spec(
                (b, cfg.num_modality_tokens, cfg.d_model), ("batch", None, None), jnp.bfloat16
            )
        if cfg.encoder_decoder:
            out["frontend"] = batch_spec(
                (b, s, cfg.d_model), ("batch", "act_seq", None), jnp.bfloat16
            )
        if shape.kind == "train":
            out["labels"] = batch_spec((b, s_text), ("batch", None))
    else:  # decode: ONE new token against a cache of seq_len
        out["tokens"] = batch_spec((b, 1), ("batch", None))
    return out


def model_flops_for(cfg: ModelConfig, shape: InputShape) -> float:
    n_active = cfg.param_counts()["active"]
    if shape.kind == "train":
        toks = shape.global_batch * shape.seq_len
        return 6.0 * n_active * toks
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch  # decode: one token per seq


def build_combo(cfg: ModelConfig, shape: InputShape, mesh, multi_pod: bool,
                variant: str = "baseline"):
    """Returns (jitted_fn, example_args_SDS, loop_trip) ready to lower.

    variant="optimized": capacity-dispatch MoE + windowed ring KV caches
    (the §Perf configuration).
    """

    opt = variant == "optimized"
    model = Model(cfg, moe_impl="capacity" if opt else "dense",
                  windowed_cache=opt, causal_skip=opt, cache_cross_kv=opt)
    rules = make_rules(mesh, RULE_OVERRIDES[shape.kind])
    params_sds = model.abstract_params()
    params_logical = model.param_logical()
    params_sh = _shardings_for(params_sds, params_logical, mesh, rules)
    params_in = _with_shardings(params_sds, params_sh)
    batch = input_specs(cfg, shape, mesh, rules)

    if shape.kind == "train":
        ocfg = AdamWConfig(moment_dtype="bfloat16")
        # gradient accumulation: bound per-microbatch activation memory for
        # the multi-hundred-B configs (production-standard; recorded in
        # EXPERIMENTS.md §Dry-run)
        active_b = cfg.param_counts()["active"]
        n_micro = 8 if active_b > 2e10 else (2 if active_b > 8e9 else 1)
        if shape.global_batch % n_micro:
            n_micro = 1

        def train_step(params, opt_state, batch):
            def micro_loss(p, mb):
                return model.loss_fn(p, mb)

            if n_micro == 1:
                (loss, metrics), grads = jax.value_and_grad(micro_loss, has_aux=True)(
                    params, batch
                )
            else:
                def split(x):
                    return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])

                micro = jax.tree.map(split, batch)

                def accum(carry, mb):
                    g_acc, l_acc = carry
                    (l, _), g = jax.value_and_grad(micro_loss, has_aux=True)(params, mb)
                    g_acc = jax.tree.map(lambda a, b: a + b.astype(a.dtype), g_acc, g)
                    return (g_acc, l_acc + l), ()

                # accumulate in param dtype: a param-sized f32 accumulator
                # (+1 f32 micro-grad) costs ~7 GB/device at 235B scale —
                # bf16 accumulation over <=4 microbatches loses <1 ulp/term
                g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), params)
                (grads, loss), _ = jax.lax.scan(accum, (g0, jnp.zeros((), jnp.float32)), micro)
                grads = jax.tree.map(lambda g: g / n_micro, grads)
                loss = loss / n_micro
            lr = linear_warmup_cosine(opt_state.step, 100, 10_000)
            new_p, new_o, om = adamw_update(grads, opt_state, params, ocfg, lr)
            return new_p, new_o, {"loss": loss, **om}

        opt_sds = jax.eval_shape(lambda p: adamw_init(p, ocfg), params_sds)
        opt_sh = type(opt_sds)(
            step=NamedSharding(mesh, P()),
            m=_shardings_for(opt_sds.m, params_logical, mesh, rules),
            v=_shardings_for(opt_sds.v, params_logical, mesh, rules),
        )
        opt_in = _with_shardings(opt_sds, opt_sh)
        fn = jax.jit(
            train_step,
            in_shardings=(params_sh, opt_sh, None),
            out_shardings=(params_sh, opt_sh, None),
            donate_argnums=(0, 1),
        )
        return fn, (params_in, opt_in, batch), model.repeats

    if shape.kind == "prefill":
        def prefill(params, batch):
            logits, cache = model.prefill(params, batch)
            return logits

        fn = jax.jit(prefill, in_shardings=(params_sh, None))
        return fn, (params_in, batch), model.repeats

    # decode: serve_step — one token, cache of seq_len
    cache_sds = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len)
    )
    cache_logical = model.cache_logical(shape.global_batch, shape.seq_len)
    cache_sh = _shardings_for(cache_sds, cache_logical, mesh, rules)
    cache_in = _with_shardings(cache_sds, cache_sh)

    def serve_step(params, token, cache):
        return model.decode_step(params, token, cache)

    fn = jax.jit(
        serve_step,
        in_shardings=(params_sh, None, cache_sh),
        out_shardings=(None, cache_sh),
        donate_argnums=(2,),
    )
    return fn, (params_in, batch["tokens"], cache_in), model.repeats


def run_combo(
    arch: str, shape_name: str, multi_pod: bool, verbose: bool = True,
    variant: str = "baseline",
) -> Dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    chips = 512 if multi_pod else 256
    t0 = clock()
    with sharding_rules(mesh, RULE_OVERRIDES[shape.kind]):
        fn, args, loop_trip = build_combo(cfg, shape, mesh, multi_pod, variant)
        with mesh:
            lowered = fn.lower(*args)
            compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo, loop_trip=loop_trip)
    mem_bytes = 0
    for attr in ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes"):
        mem_bytes += int(getattr(mem, attr, 0) or 0)
    # donated args alias outputs; subtract the double count
    alias = int(getattr(mem, "alias_size_in_bytes", 0) or 0)
    mem_bytes -= alias
    # executed flops/bytes from the analytic cost model (CPU-backend
    # cost_analysis counts while-loop bodies once — see roofline/costmodel.py)
    from repro.roofline.costmodel import estimate

    est = estimate(cfg, shape, optimized=(variant == "optimized"))
    terms = roofline_from_compiled(
        arch=arch,
        shape=shape_name,
        mesh_name=mesh_name,
        chips=chips,
        flops=est.flops,
        bytes_accessed=est.hbm_bytes,
        collective_bytes=coll["total"],
        model_flops=est.flops_model,
        # memory_analysis is for the per-device SPMD module already
        mem_per_device_bytes=mem_bytes,
    )
    rec = terms.as_dict()
    rec.update(
        compile_s=round(clock() - t0, 1),
        collective_breakdown={k: v / 1e9 for k, v in coll.items()},
        xla_cost_flops=float(cost.get("flops", 0.0)),
        xla_cost_bytes=float(cost.get("bytes accessed", 0.0)),
        variant=variant,
        status="ok",
    )
    if verbose:
        print(f"--- {arch} x {shape_name} x {mesh_name} ---")
        print("memory_analysis:", mem)
        print(
            "cost_analysis (loop-body-once): flops={:.3e} bytes={:.3e}".format(
                float(cost.get("flops", 0)), float(cost.get("bytes accessed", 0))
            )
        )
        print(
            f"roofline: compute={terms.compute_s:.4f}s memory={terms.memory_s:.4f}s "
            f"collective={terms.collective_s:.4f}s bottleneck={terms.bottleneck} "
            f"useful={terms.useful_ratio:.3f} mem/dev={terms.mem_per_device_gb:.2f}GB"
        )
    return rec


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="all")
    p.add_argument("--shape", default="all")
    p.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    p.add_argument("--out", default="results/dryrun.json")
    p.add_argument("--variant", choices=["baseline", "optimized"], default="baseline")
    p.add_argument("--force", action="store_true")
    args = p.parse_args(argv)

    archs = DRYRUN_ARCHS if args.arch == "all" else (args.arch,)
    shapes = tuple(INPUT_SHAPES) if args.shape == "all" else (args.shape,)
    pods = {"single": (False,), "multi": (True,), "both": (False, True)}[args.mesh]

    # always load previous records; --force only bypasses the cache check
    results: Dict[str, Dict] = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    failures = []
    for arch in archs:
        cfg = get_config(arch)
        for shape_name in shapes:
            shape = INPUT_SHAPES[shape_name]
            if not supports_shape(cfg, shape):
                key = f"{arch}|{shape_name}|skip"
                results[key] = {"status": "skip", "reason": "full-attention arch; see DESIGN.md §4"}
                continue
            for multi_pod in pods:
                key = f"{arch}|{shape_name}|{'pod2x16x16' if multi_pod else 'pod16x16'}"
                if args.variant != "baseline":
                    key += f"|{args.variant}"
                if key in results and results[key].get("status") == "ok" and not args.force:
                    print(f"cached: {key}")
                    continue
                try:
                    results[key] = run_combo(arch, shape_name, multi_pod, variant=args.variant)
                except Exception as e:  # noqa: BLE001 — record and continue
                    traceback.print_exc()
                    results[key] = {"status": "fail", "error": str(e)[:2000]}
                    failures.append(key)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    n_ok = sum(1 for v in results.values() if v.get("status") == "ok")
    print(f"\n{n_ok} ok / {len(results)} recorded; failures: {failures}")
    return results


if __name__ == "__main__":
    main()
