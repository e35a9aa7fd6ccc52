import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- everything below may import jax (device count is locked above) ---------
import argparse
import dataclasses
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ModelConfig, ParallelConfig, ShapeConfig
from ..configs import cells, get_config, get_shape, list_archs, LONG_CONTEXT_OK
from ..distributed.sharding import ShardingPolicy
from ..models import LM
from ..optim import AdamW
from ..train.steps import make_train_step
from .mesh import make_production_mesh

SDS = jax.ShapeDtypeStruct


# ---------------------------------------------------------------------------
# HLO collective accounting
# ---------------------------------------------------------------------------

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"\b([a-z]+\d+|pred)\[([\d,]*)\]")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo: str) -> Dict[str, Any]:
    """Sum operand bytes of every collective op in the (per-device) module."""
    per_op: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    counts: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for line in hlo.splitlines():
        if "=" not in line:
            continue
        m = re.search(r"=\s+[^\s]+\s+([a-z\-]+)(?:-start)?\(", line)
        if not m:
            continue
        op = m.group(1)
        if op.endswith("-start"):
            op = op[: -len("-start")]
        if op not in _COLLECTIVES:
            continue
        if op == "all-reduce" and ("-done" in line.split("=")[1][:40]):
            continue
        # operand shapes: everything inside the call parens
        call = line.split("(", 1)[1]
        shapes = _SHAPE_RE.findall(call)
        if not shapes:
            # fall back to the result shape(s)
            shapes = _SHAPE_RE.findall(line.split("=", 1)[1].split("(")[0])
        nbytes = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        per_op[op] += nbytes
        counts[op] += 1
    total = sum(per_op.values())
    return {"bytes_by_op": per_op, "counts": counts, "total_bytes": total}


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStructs — no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig, lm: LM):
    """Abstract inputs for one cell, as the dry-run contract requires."""
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        batch = {"tokens": SDS((B, S + 1), jnp.int32)}
        if cfg.encoder_layers:
            batch["enc_embeds"] = SDS((B, cfg.encoder_seq, cfg.d_model),
                                      jnp.float32)
        return batch
    if shape.kind == "prefill":
        out = {"tokens": SDS((B, S), jnp.int32)}
        if cfg.encoder_layers:
            out["enc_embeds"] = SDS((B, cfg.encoder_seq, cfg.d_model),
                                    jnp.float32)
        return out
    # decode: one new token against a cache of S tokens
    token = SDS((B, 1), jnp.int32)
    if cfg.encoder_layers:
        params_sds = jax.eval_shape(lm.init_params, jax.random.PRNGKey(0))  # repro: ignore[prng-literal-key] -- shape-only probe
        state = jax.eval_shape(
            lambda p: lm.init_decode_state(
                B, S,
                enc_embeds=jnp.zeros((B, cfg.encoder_seq, cfg.d_model),
                                     jnp.dtype(cfg.dtype)),
                params=p),
            params_sds)
    else:
        state = jax.eval_shape(lambda: lm.init_decode_state(B, S))
    return {"token": token, "state": state}


def serve_params_specs(lm: LM):
    """Serving params are bf16 (inference memory layout)."""
    p = jax.eval_shape(lm.init_params, jax.random.PRNGKey(0))  # repro: ignore[prng-literal-key] -- shape-only probe
    dt = jnp.dtype(lm.cfg.dtype)
    return jax.tree_util.tree_map(
        lambda a: SDS(a.shape, dt if a.dtype == jnp.float32 else a.dtype), p)


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

def _unit_size(cfg: ModelConfig) -> int:
    if cfg.hybrid_period:
        return cfg.hybrid_period
    if cfg.n_experts > 0 and cfg.moe_every > 1:
        return cfg.moe_every
    return 1


def microbatches_for(cfg: ModelConfig, shape: ShapeConfig, n_data: int) -> int:
    """Gradient-accumulation factor bounding the live per-device activation
    working set.

    Model (empirically calibrated on this CPU-backend buffer assignment,
    which schedules remat recomputes eagerly — i.e. per-unit liveness is the
    SUM over the unit's layers, not the max):
      outer-scan residuals:  n_units · tok_mb · d · 2B
      per-unit working set:  Σ_layers tok_mb · (24·d + 6·f_eff) bytes
        f_eff = d_ff (dense) | top_k·cf·d_ff (MoE) | 4·d (SSM in_proj)
    """
    if shape.kind != "train":
        return 1
    u = _unit_size(cfg)
    n_units = max(cfg.n_layers // u, 1)
    per_dev_batch = max(shape.global_batch // n_data, 1)

    def unit_bytes(tok):
        total = 0.0
        for j in range(u):
            kind = cfg.layer_kind(j)
            width = 24.0 * cfg.d_model
            if kind.value.startswith("ssm"):
                width += 24.0 * cfg.ssm_expand * cfg.d_model
            if kind.value.endswith("moe"):
                width += 6.0 * cfg.experts_per_token * cfg.capacity_factor                     * cfg.d_ff
            elif cfg.d_ff:
                width += 6.0 * cfg.d_ff
            total += tok * width
        return total

    budget = 6 * 2 ** 30
    mb = 1
    while mb < per_dev_batch and shape.global_batch % (2 * mb) == 0:
        tok = (per_dev_batch // mb) * shape.seq_len
        est = n_units * tok * cfg.d_model * 2 + unit_bytes(tok)
        if est <= budget:
            break
        mb *= 2
    return mb


def compile_once(arch: str, shape_name: str, multi_pod: bool,
                 parallel: Optional[ParallelConfig] = None,
                 cfg_overrides: Optional[dict] = None,
                 force_microbatches: Optional[int] = None):
    """Lower + compile one configuration; returns (record_fragment, compiled)."""
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = get_shape(shape_name)
    lm = LM(cfg)
    policy = ShardingPolicy(mesh, cfg, parallel)
    n_chips = int(np.prod(list(mesh.shape.values())))

    record: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": dict(mesh.shape), "chips": n_chips,
        "kind": shape.kind, "seq_len": shape.seq_len,
        # what the compile targeted: benchmarks/roofline.py keys its peaks
        # by this and refuses kinds it has no published numbers for
        "device_kind": mesh.devices.flat[0].device_kind,
        "global_batch": shape.global_batch,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
    }

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            params_s = jax.eval_shape(lm.init_params, jax.random.PRNGKey(0))  # repro: ignore[prng-literal-key] -- shape-only probe
            opt = AdamW()
            opt_s = jax.eval_shape(opt.init, params_s)
            batch_s = input_specs(cfg, shape, lm)
            p_sh = policy.params_shardings(params_s)
            o_sh = jax.tree_util.tree_map(
                lambda l: policy.params_shardings(l) if hasattr(l, "shape") else l,
                opt_s)
            # opt state: m, v shard like params; step replicated
            from ..optim import OptState
            o_sh = OptState(step=policy.replicated(),
                            m=policy.params_shardings(opt_s.m),
                            v=policy.params_shardings(opt_s.v))
            b_sh = policy.batch_shardings(batch_s)
            n_data = 1
            for a in policy.dp:
                n_data *= mesh.shape[a]
            # cost probes must run mb=1: the grad-accumulation scan is a
            # while loop whose body HLO cost analysis counts exactly once.
            mb = force_microbatches or microbatches_for(cfg, shape, n_data)
            record["microbatches"] = mb
            step_fn = make_train_step(lm, opt, microbatches=mb)
            rep = policy.replicated()
            jitted = jax.jit(
                step_fn,
                in_shardings=(p_sh, o_sh, b_sh),
                out_shardings=(p_sh, o_sh, {"loss": rep, "grad_norm": rep,
                                            "step": rep}),
                donate_argnums=(0, 1))
            lowered = jitted.lower(params_s, opt_s, batch_s)
        elif shape.kind == "prefill":
            params_s = serve_params_specs(lm)
            p_sh = policy.params_shardings(params_s)
            ins = input_specs(cfg, shape, lm)
            state_s = jax.eval_shape(
                lambda p, t, e: lm.prefill(p, t, enc_embeds=e),
                params_s, ins["tokens"], ins.get("enc_embeds"))
            out_sh = (policy.logits_shardings(shape.global_batch),
                      policy.decode_state_shardings(state_s[1]))
            b_sh = policy.batch_shardings(ins)
            in_sh = [p_sh, b_sh["tokens"]]
            lower_args = [params_s, ins["tokens"]]
            if "enc_embeds" in ins:
                in_sh.append(b_sh["enc_embeds"])
                lower_args.append(ins["enc_embeds"])

            def prefill_fn(p, t, e=None):
                return lm.prefill(p, t, enc_embeds=e)

            jitted = jax.jit(prefill_fn, in_shardings=tuple(in_sh),
                             out_shardings=out_sh)
            lowered = jitted.lower(*lower_args)
        else:  # decode
            params_s = serve_params_specs(lm)
            p_sh = policy.params_shardings(params_s)
            ins = input_specs(cfg, shape, lm)
            st_sh = policy.decode_state_shardings(ins["state"])
            tok_sh = policy.batch_shardings({"token": ins["token"]})["token"]
            jitted = jax.jit(
                lm.decode_step,
                in_shardings=(p_sh, tok_sh, st_sh),
                out_shardings=(policy.logits_shardings(shape.global_batch),
                               st_sh),
                donate_argnums=(2,))
            lowered = jitted.lower(params_s, ins["token"], ins["state"])

        record["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t1, 2)

    mem = compiled.memory_analysis()
    if mem is not None:
        for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                record[k] = int(v)
    cost = compiled.cost_analysis()
    if cost:
        record["flops_per_device"] = float(cost.get("flops", -1))
        record["bytes_accessed_per_device"] = float(cost.get("bytes accessed", -1))
        record["transcendentals"] = float(cost.get("transcendentals", -1))
    record["collectives"] = parse_collectives(compiled.as_text())
    return record, cfg


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               parallel: Optional[ParallelConfig] = None,
               cfg_overrides: Optional[dict] = None,
               extrapolate: bool = True) -> Dict[str, Any]:
    """Full-cell dry-run record.

    The full-depth scanned compile provides the sharding/memory proof; HLO
    cost analysis counts while-loop bodies ONCE, so true per-step costs are
    obtained from two small fully-unrolled compiles at depth 1·unit and
    2·unit, extrapolated linearly in depth:

        total(D) = c1 + (D - 1) * (c2 - c1)        [per-device]

    applied to flops, bytes accessed, transcendentals and per-op collective
    bytes/counts. Reported as *_extrapolated alongside the raw numbers.
    """
    record, cfg = compile_once(arch, shape_name, multi_pod, parallel,
                               cfg_overrides)
    if not extrapolate:
        return record
    u = _unit_size(cfg)
    n_units = cfg.n_layers // u
    if n_units < 2:
        record["flops_extrapolated"] = record.get("flops_per_device")
        record["bytes_extrapolated"] = record.get("bytes_accessed_per_device")
        record["collective_bytes_extrapolated"] = \
            record["collectives"]["total_bytes"]
        return record

    def depth_overrides(mult: int) -> dict:
        ov = dict(cfg_overrides or {})
        ov["n_layers"] = mult * u
        ov["unroll_scans"] = True
        if cfg.encoder_layers:
            ov["encoder_layers"] = mult
        return ov

    r1, _ = compile_once(arch, shape_name, multi_pod, parallel,
                         depth_overrides(1), force_microbatches=1)
    r2, _ = compile_once(arch, shape_name, multi_pod, parallel,
                         depth_overrides(2), force_microbatches=1)

    def extr(key, d=None):
        v1 = r1.get(key) if d is None else r1[d][key]
        v2 = r2.get(key) if d is None else r2[d][key]
        if v1 is None or v2 is None:
            return None
        # clamp: per-unit deltas are physically non-negative; occasional
        # d1-only resharding artifacts would otherwise extrapolate negative
        return v1 + (n_units - 1) * max(v2 - v1, 0.0)

    record["flops_extrapolated"] = extr("flops_per_device")
    record["bytes_extrapolated"] = extr("bytes_accessed_per_device")
    record["transcendentals_extrapolated"] = extr("transcendentals")
    coll = {}
    for op in _COLLECTIVES:
        v1 = r1["collectives"]["bytes_by_op"][op]
        v2 = r2["collectives"]["bytes_by_op"][op]
        coll[op] = v1 + (n_units - 1) * max(v2 - v1, 0)
    record["collectives_extrapolated"] = {
        "bytes_by_op": coll, "total_bytes": sum(coll.values())}
    record["collective_bytes_extrapolated"] = sum(coll.values())
    record["depth_probe_compile_s"] = [r1["compile_s"], r2["compile_s"]]
    return record


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="both")
    ap.add_argument("--out", default="benchmarks/results/dryrun.json")
    ap.add_argument("--print-hlo", action="store_true")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else [args.arch]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["chips"]) for r in results
            if "error" not in r}

    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]
    todo = []
    for arch, shape_name in cells():
        if args.arch != "all" and arch != args.arch:
            continue
        if args.shape != "all" and shape_name != args.shape:
            continue
        for mp in meshes:
            chips = 512 if mp else 256
            if (arch, shape_name, chips) in done:
                continue
            todo.append((arch, shape_name, mp))

    print(f"dry-run: {len(todo)} cells to lower+compile", flush=True)
    for i, (arch, shape_name, mp) in enumerate(todo):
        tag = f"{arch} x {shape_name} x {'2x16x16' if mp else '16x16'}"
        print(f"[{i+1}/{len(todo)}] {tag} ...", flush=True)
        try:
            rec = lower_cell(arch, shape_name, mp)
            print(f"    ok: compile {rec['compile_s']}s, "
                  f"flops/dev {rec.get('flops_per_device', 0):.3e}, "
                  f"coll {rec['collectives']['total_bytes']/2**20:.1f} MiB",
                  flush=True)
        except Exception as e:
            rec = {"arch": arch, "shape": shape_name,
                   "chips": 512 if mp else 256,
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
            print(f"    FAILED: {rec['error'][:200]}", flush=True)
        results = [r for r in results
                   if not (r["arch"] == rec["arch"] and r["shape"] == rec["shape"]
                           and r["chips"] == rec["chips"])]
        results.append(rec)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    print("dry-run complete", flush=True)


if __name__ == "__main__":
    main()
