import os
# a CPU compile tool: 512 host devices stand in for the production mesh, and
# the accelerator (if the machine has one) is left to the serving processes
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- everything below may import jax (platform and device count are locked
# above) ---------------------------------------------------------------------
import argparse          # noqa: E402
import json              # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from pathlib import Path  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import configs                                   # noqa: E402
from repro.configs.base import SHAPES                        # noqa: E402
from repro.launch import specs as specs_mod                  # noqa: E402
from repro.launch.mesh import make_production_mesh           # noqa: E402
from repro.models import decode_step, prefill                # noqa: E402
from repro.optim.adamw import AdamW                          # noqa: E402
from repro.roofline import analysis as roofline              # noqa: E402
from repro.sharding import partition as part                 # noqa: E402
from repro.sharding.api import activation_sharding           # noqa: E402
from repro.training.train_step import make_train_step        # noqa: E402

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"


def _named_rules(mesh, mode):
    rules = part.activation_rules(mesh, mode=mode)
    return {k: (NamedSharding(mesh, v) if v is not None else None)
            for k, v in rules.items()}


def _effective_microbatches(cfg, batch: int, dp_size: int) -> int:
    m = max(1, cfg.microbatches)
    while m > 1 and not (batch % m == 0 and (batch // m) % dp_size == 0):
        m -= 1
    return m


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               quantized: bool = False, donate: bool = True,
               cfg_override=None, policy: str = "tp", kv8: bool = False):
    """Lower + compile one (arch × shape × mesh) cell; return artifacts."""
    cfg = cfg_override if cfg_override is not None \
        else configs.get_config(arch)
    if kv8:
        cfg = cfg.scaled(kv_cache_dtype="int8")
    info = SHAPES[shape_name]
    with part.parallelism_policy(policy):
        return _lower_cell_inner(arch, shape_name, cfg, info,
                                 multi_pod=multi_pod, quantized=quantized,
                                 donate=donate)


def _lower_cell_inner(arch, shape_name, cfg, info, *, multi_pod, quantized,
                      donate):
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    dp = part._axis_size(mesh, part.dp_axes(mesh))
    kind = info["kind"]

    if quantized:
        params_s = specs_mod.quantized_params_specs(cfg)
    else:
        params_s = specs_mod.params_specs(cfg)
    params_p = part.param_pspecs(params_s, mesh)
    params_sh = part.named(params_p, mesh)

    if kind == "train":
        m_eff = _effective_microbatches(cfg, info["global_batch"], dp)
        if m_eff != cfg.microbatches:
            cfg = cfg.scaled(microbatches=m_eff)
        opt = AdamW(lr=3e-4, moment_dtype=cfg.optimizer_dtype)
        opt_s = jax.eval_shape(opt.init, params_s)
        state_s = {"params": params_s, "opt": opt_s,
                   "step": jax.ShapeDtypeStruct((), jnp.int32)}
        opt_sh = {"m": params_sh, "v": params_sh,
                  "count": NamedSharding(mesh, P())}
        state_sh = {"params": params_sh, "opt": opt_sh,
                    "step": NamedSharding(mesh, P())}
        batch_s = specs_mod.batch_specs(cfg, shape_name)
        batch_sh = part.named(part.batch_pspecs(batch_s, mesh), mesh)
        fn = make_train_step(cfg, opt)
        metrics_sh = {"loss": NamedSharding(mesh, P())}
        with mesh, activation_sharding(_named_rules(mesh, "train")):
            jitted = jax.jit(
                fn, in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, metrics_sh),
                donate_argnums=(0,) if donate else ())
            lowered = jitted.lower(state_s, batch_s)
    elif kind == "prefill":
        batch_s = specs_mod.batch_specs(cfg, shape_name)
        batch_sh = part.named(part.batch_pspecs(batch_s, mesh), mesh)
        seq = info["seq_len"]
        state_out_s = jax.eval_shape(
            lambda: None) if False else None  # structure from prefill itself
        def fn(params, batch):
            return prefill(params, cfg, batch, capacity=seq)
        # output shardings: logits + decode-state rules
        import functools
        from repro.models import init_decode_state
        b = info["global_batch"]
        st_s = jax.eval_shape(lambda: init_decode_state(cfg, b, seq))
        st_sh = part.named(
            part.state_pspecs(st_s, mesh, sequence_sharded=False), mesh)
        logits_sh = NamedSharding(mesh, P(part.dp_axes(mesh), "model"))
        with mesh, activation_sharding(_named_rules(mesh, "prefill")):
            jitted = jax.jit(fn, in_shardings=(params_sh, batch_sh),
                             out_shardings=(logits_sh, st_sh))
            lowered = jitted.lower(params_s, batch_s)
    else:  # decode
        seq_sharded = shape_name == "long_500k"
        state_s, tok_s = specs_mod.decode_state_specs(cfg, shape_name)
        state_sh = part.named(
            part.state_pspecs(state_s, mesh, sequence_sharded=seq_sharded),
            mesh)
        dp_ax = part.dp_axes(mesh)
        b = info["global_batch"]
        tok_spec = ((part._maybe(mesh, dp_ax, b),) +
                    (None,) * (len(tok_s.shape) - 1))
        tok_sh = NamedSharding(mesh, P(*tok_spec))
        logits_sh = NamedSharding(
            mesh, P(part._maybe(mesh, dp_ax, b), "model"))
        mode = "decode_long" if seq_sharded else "decode"

        def fn(params, state, tokens):
            return decode_step(params, cfg, state, tokens)

        with mesh, activation_sharding(_named_rules(mesh, mode)):
            jitted = jax.jit(
                fn, in_shardings=(params_sh, state_sh, tok_sh),
                out_shardings=(logits_sh, state_sh),
                donate_argnums=(1,) if donate else ())
            lowered = jitted.lower(params_s, state_s, tok_s)

    compiled = lowered.compile()
    extras = {"dequant_temp_bytes_per_chip":
              _dequant_temp_bytes(params_s, params_sh) if quantized else 0.0}
    if kind == "decode" and cfg.kv_cache_dtype == "int8":
        extras["cache_dequant_bytes_per_chip"] = _cache_dequant_bytes(
            state_s, state_sh)
    return cfg, mesh, lowered, compiled, extras


def _dequant_temp_bytes(params_s, params_sh) -> float:
    """Per-chip HBM traffic of the XLA grouped backend's unpack temps, which
    the Pallas ternary_matmul kernel eliminates (hillclimb iteration 4).

    The grouped path materializes both trit-planes as bf16 before the dot:
    per plane shard, 4 trits/packed-byte × 2 B × (write + read) = 16× the
    packed shard bytes. The Pallas kernel (kernels/ternary_matmul, validated
    vs the jnp oracle) reads the PACKED bytes into VMEM and unpacks
    in-register, so its HBM traffic excludes these temps entirely.
    """
    import numpy as _np

    from repro.core.quantize_model import QuantizedKernel as _QK

    total = 0.0

    def walk(spec_node, sh_node):
        nonlocal total
        if isinstance(spec_node, _QK):
            for buf, sh in ((spec_node.t1p, sh_node.t1p),
                            (spec_node.t2p, sh_node.t2p)):
                shard = sh.shard_shape(buf.shape) if sh is not None \
                    else buf.shape
                packed_bytes = float(_np.prod(shard))  # uint8
                total += 16.0 * packed_bytes
            return
        if isinstance(spec_node, dict):
            for k in spec_node:
                walk(spec_node[k], sh_node[k])

    walk(params_s, params_sh)
    return total


def _cache_dequant_bytes(state_s, state_sh) -> float:
    """Per-chip traffic of int8-KV dequant temps (4 B per cached element:
    bf16 write + read), which a fused int8 decode-attention kernel removes
    (§Perf it. 5) — same accounting pattern as _dequant_temp_bytes."""
    import numpy as _np

    total = 0.0

    def walk(spec_node, sh_node, path=""):
        nonlocal total
        if isinstance(spec_node, dict):
            for k in spec_node:
                walk(spec_node[k], sh_node[k], f"{path}/{k}")
            return
        name = path.rsplit("/", 1)[-1]
        if name in ("k", "v") and spec_node.dtype == jnp.int8:
            shard = sh_node.shard_shape(spec_node.shape) \
                if sh_node is not None else spec_node.shape
            total += 4.0 * float(_np.prod(shard))

    walk(state_s, state_sh)
    return total


def choose_policy(arch: str, shape_name: str, multi_pod: bool = False) -> str:
    """Arch-aware parallelism (hillclimb it. 2): pick fsdp_all for a train
    cell when FSDP's param-all-gather traffic undercuts TP's per-layer
    activation all-reduces.

    Napkin model (per chip per step, bf16):
      TP    ≈ 6 collectives/layer × tokens_per_chip × d_model × 2 B
              (fwd + remat-recompute + bwd-dx, attn-out + mlp-out each)
      FSDP  ≈ 4 B × total_params — measured (EXPERIMENTS §Perf it. 2b):
              XLA CSEs the param all-gathers across fwd/remat/bwd, so the
              realized cost is ~2 bf16 traversals (gather + grad
              reduce-scatter), not the naive 4 traversals
    """
    cfg = configs.get_config(arch)
    info = SHAPES[shape_name]
    n_chips = 512 if multi_pod else 256
    if info["kind"] != "train" or info["global_batch"] % n_chips != 0:
        return "tp"
    total, _ = cfg.param_counts()
    dp_under_tp = n_chips // 16
    tokens_per_chip = info["global_batch"] * info["seq_len"] / dp_under_tp
    tp_bytes = 6 * cfg.n_layers * tokens_per_chip * cfg.d_model * 2
    fsdp_bytes = 4 * total
    return "fsdp_all" if fsdp_bytes < tp_bytes else "tp"


def _bf16_promo(cfg) -> float:
    """The CPU backend promotes bf16 compute to f32 (verified on a bare bf16
    dot: internal buffers + collectives appear as f32). Interface args/outputs
    keep bf16, but temps and collective payloads double. For bf16-activation
    models we therefore scale temp-traffic and collective bytes by 0.5 to
    recover the TPU-dtype numbers (EXPERIMENTS.md §Perf iteration 0)."""
    return 0.5 if cfg.activation_dtype == "bfloat16" else 1.0


def _traffic_bytes(compiled, promo: float = 1.0):
    """(traffic, interface) HBM-byte proxies.

    traffic   = args + outputs + 2×temps (each temp written once + read once;
                temps scaled by the bf16-promotion factor). The roofline
                memory term. Per-op operand sums ("bytes accessed") count
                every fusion-internal edge — 10-30× pessimistic vs a fusing
                TPU backend — so we use this allocation proxy (both reported).
    interface = args + outputs only: the PERFECT-FUSION streaming floor —
                what hand-written kernels (Pallas ternary matmul, fused
                int8-KV decode attention) approach, with all temps in VMEM.
    """
    try:
        mem = compiled.memory_analysis()
        args = float(getattr(mem, "argument_size_in_bytes", 0) or 0)
        outs = float(getattr(mem, "output_size_in_bytes", 0) or 0)
        temps = float(getattr(mem, "temp_size_in_bytes", 0) or 0)
        return args + outs + 2.0 * promo * temps, args + outs
    except Exception:  # noqa: BLE001
        return 0.0, 0.0


def _cost_analysis(compiled):
    """compiled.cost_analysis() as a flat dict — newer jax returns a
    one-element list of per-computation dicts, older jax the dict itself."""
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost


def _cell_costs(compiled, promo: float = 1.0):
    """(flops, op-bytes, (traffic, interface)-bytes, per-op coll bytes)."""
    cost = _cost_analysis(compiled)
    coll = roofline.parse_collectives(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            _traffic_bytes(compiled, promo),
            {k: v * promo for k, v in coll.by_op.items()})


def scan_corrected_costs(arch, shape_name, *, multi_pod, quantized,
                         policy: str = "tp", kv8: bool = False):
    """Exact per-step costs, correcting XLA's count-scan-body-once bias.

    cost_analysis() counts a ``lax.scan`` body exactly once regardless of
    trip count (verified empirically — see EXPERIMENTS.md §Perf iteration 0),
    so deep scanned models under-report FLOPs/bytes/collectives by ~n_periods.
    We lower two small UNROLLED variants (k=1 and k=2 periods, microbatches=1)
    with identical prefix/remainder/embed/head structure:

        body = cost(k=2) - cost(k=1);  true = cost(k=1) + (N-1) * body
    """
    cfg = configs.get_config(arch)
    if cfg.n_periods <= 1 and cfg.microbatches <= 1:
        return None  # nothing to correct

    promo = _bf16_promo(cfg)

    def variant(k):
        n_layers = (len(cfg.prefix_pattern) + k * cfg.period
                    + len(cfg.remainder_pattern))
        vcfg = cfg.scaled(n_layers=n_layers, scan_layers=False,
                          microbatches=1,
                          **({"kv_cache_dtype": "int8"} if kv8 else {}))
        _, _, _, compiled, _ = lower_cell(
            arch, shape_name, multi_pod=multi_pod, quantized=quantized,
            cfg_override=vcfg, policy=policy)
        return _cell_costs(compiled, promo)

    f1, b1, (t1, i1), c1 = variant(1)
    f2, b2, (t2, i2), c2 = variant(2)
    n = cfg.n_periods
    flops = f1 + (n - 1) * (f2 - f1)
    nbytes = b1 + (n - 1) * (b2 - b1)
    traffic = t1 + (n - 1) * (t2 - t1)
    interface = i1 + (n - 1) * (i2 - i1)
    coll = {k: c1[k] + (n - 1) * (c2[k] - c1[k]) for k in c1}
    return {"flops": flops, "bytes": nbytes, "traffic": traffic,
            "interface": interface, "collectives": coll,
            "variant1": {"flops": f1, "bytes": b1, "traffic": t1,
                         "collectives": c1},
            "variant2": {"flops": f2, "bytes": b2, "traffic": t2,
                         "collectives": c2}}


def analyze(arch, shape_name, cfg, mesh, lowered, compiled, *, quantized,
            lower_s, compile_s, corrected=None, extras=None):
    info = SHAPES[shape_name]
    n_chips = mesh.size
    cost = _cost_analysis(compiled)
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        }
    except Exception as e:  # noqa: BLE001
        mem_d = {"error": str(e)}

    hlo = compiled.as_text()
    coll = roofline.parse_collectives(hlo)

    if corrected is not None:  # scan-corrected exact costs (see §Perf it. 0)
        flops_dev = corrected["flops"]
        bytes_dev = corrected["bytes"]
        traffic_dev = corrected["traffic"]
        interface_dev = corrected["interface"]
        coll_dev = float(sum(corrected["collectives"].values()))
        coll_by_op = corrected["collectives"]
    else:
        promo = _bf16_promo(cfg)
        flops_dev = float(cost.get("flops", 0.0))
        bytes_dev = float(cost.get("bytes accessed", 0.0))
        traffic_dev, interface_dev = _traffic_bytes(compiled, promo)
        coll_dev = float(coll.total_bytes) * promo
        coll_by_op = {k: v * promo for k, v in coll.by_op.items()}
    # memory term = allocation-traffic proxy; operand-sum kept for reference
    terms = roofline.roofline_terms(flops_dev, traffic_dev, coll_dev)
    terms["memory_opsum_s"] = bytes_dev / roofline.HBM_BW
    # fused-kernel memory floor (it. 4/5): perfect-fusion streaming bound —
    # every buffer crosses HBM exactly once (args + outputs; temps in VMEM).
    # The Pallas ternary matmul / a fused int8-KV decode-attention kernel
    # approach this bound; the XLA grouped path pays the dequant temps.
    fused = roofline.roofline_terms(flops_dev, interface_dev, coll_dev)
    terms["memory_fused_s"] = fused["memory_s"]
    terms["dominant_fused"] = fused["dominant"]
    terms["step_lower_bound_fused_s"] = fused["step_lower_bound_s"]
    mf = roofline.model_flops(cfg, info, train=(info["kind"] == "train"))

    result = {
        "arch": arch,
        "shape": shape_name,
        "policy": part.current_policy(),
        "mesh": list(mesh.shape.values()),
        "axes": list(mesh.axis_names),
        "n_chips": n_chips,
        "quantized": quantized,
        "kind": info["kind"],
        "cost_analysis": {k: v for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "corrected": corrected is not None,
        "flops_per_chip": flops_dev,
        "bytes_per_chip": bytes_dev,
        "traffic_bytes_per_chip": traffic_dev,
        "collective_bytes_per_chip": coll_dev,
        "memory_analysis": mem_d,
        "collectives": {"total_bytes": coll_dev, "by_op": coll_by_op,
                        "raw_scanned": coll.to_dict()},
        "roofline": terms,
        "model_flops_global": mf,
        "model_flops_per_chip": mf / n_chips,
        "useful_flops_ratio": (mf / n_chips) / flops_dev if flops_dev else None,
        "lower_s": lower_s,
        "compile_s": compile_s,
        "hlo_bytes": len(hlo),
    }
    return result


def run_cell(arch, shape_name, mesh_kind, quantized, out_dir: Path,
             policy: str = "auto", kv8: bool = False):
    t0 = time.time()
    multi = mesh_kind == "multi"
    if policy == "auto":
        policy = choose_policy(arch, shape_name, multi_pod=multi)
    t_lower0 = time.time()
    cfg, mesh, lowered, compiled, extras = lower_cell(
        arch, shape_name, multi_pod=multi, quantized=quantized, policy=policy,
        kv8=kv8)
    t_done = time.time()
    corrected = scan_corrected_costs(arch, shape_name, multi_pod=multi,
                                     quantized=quantized, policy=policy,
                                     kv8=kv8)
    res = analyze(arch, shape_name, cfg, mesh, lowered, compiled,
                  quantized=quantized, lower_s=t_done - t_lower0,
                  compile_s=t_done - t_lower0, corrected=corrected,
                  extras=extras)
    mem = res["memory_analysis"]
    print(f"memory_analysis: {mem}")
    print(f"cost_analysis: flops={res['cost_analysis'].get('flops')} "
          f"bytes={res['cost_analysis'].get('bytes accessed')}")
    print(f"collectives: {res['collectives']['by_op']}")
    print(f"roofline: {res['roofline']}")
    out_dir.mkdir(parents=True, exist_ok=True)
    tag = (f"{arch}__{shape_name}__{mesh_kind}" + ("__q" if quantized else "")
           + ("__kv8" if kv8 else ""))
    with open(out_dir / f"{tag}.json", "w") as f:
        json.dump(res, f, indent=1)
    print(f"[dryrun] {tag} OK in {time.time() - t0:.1f}s "
          f"(dominant={res['roofline']['dominant']})")
    return res


def main(argv=None):
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", choices=configs.ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--quantized", action="store_true",
                    help="serve with PTQTP-quantized weights (paper path)")
    ap.add_argument("--policy", choices=("auto", "tp", "fsdp_all"),
                    default="tp", help="parallelism policy (§Perf it. 2)")
    ap.add_argument("--kv8", action="store_true",
                    help="int8 KV cache (§Perf it. 5, beyond-paper)")
    ap.add_argument("--out", default=str(RESULTS_DIR))
    args = ap.parse_args(argv)
    run_cell(args.arch, args.shape, args.mesh, args.quantized,
             Path(args.out), policy=args.policy, kv8=args.kv8)


if __name__ == "__main__":
    main()
