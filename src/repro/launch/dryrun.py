import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# (the two lines above MUST run before any jax import — jax locks the device
# count at first initialization)
import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

import jax               # noqa: E402

from ..configs import ARCH_IDS, get_config                      # noqa: E402
from ..distributed import use_sharding                          # noqa: E402
from ..distributed.sharding import (cache_shardings,            # noqa: E402
                                    param_shardings,
                                    step_in_shardings)
from ..models import Model, shape_by_name                       # noqa: E402
from ..models.config import ALL_SHAPES                          # noqa: E402
from ..training import AdamWConfig, adamw_init, make_train_step  # noqa: E402
from ..training.train_step import settings_for                  # noqa: E402
from .mesh import make_production_mesh                          # noqa: E402
from .roofline import extract_terms, model_flops_estimate       # noqa: E402

RESULTS_PATH = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "launch_results", "dryrun.json")


def _rules_for(arch: str, kind: str) -> Optional[Dict[str, Any]]:
    """Per-arch logical-rule overrides: big archs shard the remat-saved
    scan carry over "model" during training (activation memory / 16)."""
    st = settings_for(arch)
    if st.seq_shard_activations and kind == "train":
        return {"carry_seq": "model"}
    return None


def _mem_report(compiled) -> Dict[str, Any]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        v = getattr(ma, attr, None)
        if v is not None:
            out[attr] = int(v)
    total = (out.get("argument_size_in_bytes", 0)
             + out.get("temp_size_in_bytes", 0)
             + out.get("output_size_in_bytes", 0)
             - out.get("alias_size_in_bytes", 0))
    out["per_device_total_bytes"] = total
    return out


def _f16_shadow(cfg, settings):
    """Identical-buffer-size model in f16 for TPU-corrected memory readings.

    XLA CPU's float-normalization pass promotes bf16 while-loop buffers to
    f32 (verified: the same scan compiled in f16 has no duplicates), so
    bf16 memory_analysis over-reports vs a real TPU.  f16 has the same
    byte-width as bf16 and is CPU-native, giving the true footprint.
    """
    import dataclasses
    remap = lambda d: "float16" if d == "bfloat16" else d
    cfg2 = cfg.with_(param_dtype=remap(cfg.param_dtype),
                     compute_dtype=remap(cfg.compute_dtype))
    st2 = dataclasses.replace(
        settings, grad_dtype=remap(settings.grad_dtype),
        opt_state_dtype=remap(settings.opt_state_dtype))
    return cfg2, st2


# §Perf hillclimb variants: config/settings overrides lowered side by side
# with the baseline (results keyed "<arch>|<shape>|<mesh>#<variant>")
VARIANTS: Dict[str, Dict[str, Any]] = {
    "attn_chunk512": {"cfg": {"attn_chunk_threshold": 512}},
    "attn_chunk1024": {"cfg": {"attn_chunk_threshold": 1024}},
    "compress_pod": {"settings": {"compress_grads": True}},
    "adafactor": {"settings": {"optimizer": "adafactor",
                               "opt_state_dtype": "bfloat16"}},
    "carry_seq_off": {"rules": {"carry_seq": None}},
    "xla_flash": {"cfg": {"attn_online": True}},
    "expert_split2": {"cfg": {"moe_expert_split": 2}},
    "accum4": {"settings": {"accum_steps": 4}},
    "accum2": {"settings": {"accum_steps": 2}},
    # small models: replicate weights, give BOTH mesh axes to the batch
    # (0.5B x 256-way TP+FSDP is pure overhead)
    "pure_dp": {"settings": {"accum_steps": 1},
                "rules": {"batch": ("data", "model"), "wtp": None,
                          "fsdp": None, "tp": None, "experts": None,
                          "kv_seq": None, "carry_seq": None, "seq": None}},
}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True, f16_shadow: bool = False,
             variant: Optional[str] = None,
             hlo_dir: Optional[str] = None) -> Dict[str, Any]:
    """Lower + compile one (arch x shape x mesh) cell; return its record.
    With ``hlo_dir`` the compiled HLO is kept there for ``--reterm``."""
    cfg = get_config(arch)
    shape = shape_by_name(shape_name)
    mesh_name = "pod2" if multi_pod else "pod1"
    vspec = VARIANTS.get(variant or "", {})
    if vspec.get("cfg"):
        cfg = cfg.with_(**vspec["cfg"])

    # ---- skip rules (documented in DESIGN.md §Arch-applicability)
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return {"status": "skip",
                "reason": "quadratic full-attention arch; 500k dense KV "
                          "attention is not servable without a "
                          "sub-quadratic mechanism"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    settings = settings_for(arch)
    if vspec.get("settings"):
        import dataclasses
        settings = dataclasses.replace(settings, **vspec["settings"])
    if shape.kind == "train":
        # microbatch must stay shardable over the DP axes of this mesh
        import dataclasses
        dp = mesh.shape.get("pod", 1) * mesh.shape.get("data", 1)
        max_accum = max(shape.global_batch // dp, 1)
        if settings.accum_steps > max_accum:
            settings = dataclasses.replace(settings, accum_steps=max_accum)
    if f16_shadow:
        cfg, settings = _f16_shadow(cfg, settings)
    model = Model(cfg)
    rules = _rules_for(arch, shape.kind)
    if vspec.get("rules"):
        rules = {**(rules or {}), **vspec["rules"]}

    t0 = time.time()
    with use_sharding(mesh, rules) as ctx:
        params_abs = model.abstract_params()
        p_sh = param_shardings(ctx, params_abs)
        specs = model.input_specs(shape)
        in_sh = step_in_shardings(ctx, model, shape, specs)

        if shape.kind == "train":
            opt_cfg = AdamWConfig(state_dtype=settings.opt_state_dtype)
            from ..training.optimizer import make_optimizer
            opt_init, _ = make_optimizer(settings.optimizer, opt_cfg)
            opt_abs = jax.eval_shape(opt_init, params_abs)
            if settings.optimizer == "adafactor":
                from ..distributed.sharding import param_shardings as _ps
                o_sh = jax.tree.map(
                    lambda l: ctx.sharding((None,) * len(l.shape), l.shape),
                    opt_abs)
                o_sh["m"] = p_sh
            else:
                o_sh = {"m": p_sh, "v": p_sh,
                        "step": ctx.sharding((), ())}
            step = make_train_step(model, opt_cfg, settings,
                                   mesh=mesh)
            jitted = jax.jit(step,
                             in_shardings=(p_sh, o_sh, in_sh["batch"]),
                             out_shardings=(p_sh, o_sh, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_abs, opt_abs, specs["batch"])
            tokens = (specs["batch"]["labels"].shape[0]
                      * specs["batch"]["labels"].shape[1])
            mf = model_flops_estimate(model.active_params(), tokens, "train")
        elif shape.kind == "prefill":
            logits_sh = ctx.sharding(("batch", "tp"),
                                     (shape.global_batch, cfg.vocab_size))
            cache_abs = jax.eval_shape(
                lambda p, i: model.prefill(p, i)[1], params_abs,
                specs["inputs"])
            c_sh = cache_shardings(ctx, cfg, cache_abs)
            jitted = jax.jit(model.prefill,
                             in_shardings=(p_sh, in_sh["inputs"]),
                             out_shardings=(logits_sh, c_sh))
            lowered = jitted.lower(params_abs, specs["inputs"])
            tokens = shape.global_batch * shape.seq_len
            mf = model_flops_estimate(model.active_params(), tokens,
                                      "prefill")
        else:  # decode
            logits_sh = ctx.sharding(("batch", "tp"),
                                     (shape.global_batch, cfg.vocab_size))
            c_sh = in_sh["cache"]
            jitted = jax.jit(model.decode_step,
                             in_shardings=(p_sh, c_sh,
                                           in_sh["tokens"], in_sh["pos"]),
                             out_shardings=(logits_sh, c_sh),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_abs, specs["cache"],
                                   specs["tokens"], specs["pos"])
            tokens = shape.global_batch
            mf = model_flops_estimate(model.active_params(), tokens,
                                      "decode")
        t_lower = time.time() - t0

        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = _mem_report(compiled)
    terms = extract_terms(compiled, n_chips, mf)
    if hlo_dir is not None:
        _save_hlo(hlo_dir, arch, shape_name, mesh_name, variant,
                  compiled.as_text(), n_chips, mf)
    record = {
        "status": "ok",
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem,
        "roofline": terms.to_dict(),
    }
    if not f16_shadow:
        # TPU-corrected memory via the f16 shadow compile (same byte widths,
        # no CPU float-normalization f32 promotion of bf16 loop buffers)
        try:
            shadow = run_cell(arch, shape_name, multi_pod, verbose=False,
                              f16_shadow=True, variant=variant)
            record["memory_tpu_corrected"] = shadow.get("memory", {})
        except Exception as e:  # shadow failure is non-fatal
            record["memory_tpu_corrected"] = {"error": str(e)}
    if verbose:
        corr = record.get("memory_tpu_corrected", {}) \
            .get("per_device_total_bytes", 0)
        print(f"[{arch} x {shape_name} x {mesh_name}] "
              f"compile={t_compile:.0f}s "
              f"mem/dev={mem.get('per_device_total_bytes', 0) / 2**30:.2f}GiB"
              f" (tpu~{corr / 2**30:.2f}GiB) "
              f"flops/dev={terms.flops:.3e} "
              f"coll/dev={terms.collective_bytes / 2**20:.1f}MiB "
              f"dominant={terms.dominant}", flush=True)
        print(f"  memory_analysis: {mem}", flush=True)
        ca = {k: v for k, v in (compiled.cost_analysis() or {}).items()
              if k in ("flops", "bytes accessed")}
        print(f"  cost_analysis: {ca}", flush=True)
    return record


def _hlo_dir(results_path: str) -> str:
    """Cached HLO lives in ``hlo/`` beside the results file."""
    return os.path.join(os.path.dirname(os.path.abspath(results_path)), "hlo")


def _hlo_path(hlo_dir: str, key: str) -> str:
    return os.path.join(hlo_dir,
                        key.replace("|", "__").replace("#", "--") + ".hlo.gz")


def _save_hlo(hlo_dir, arch, shape_name, mesh_name, variant, text, n_chips,
              mf):
    import gzip
    key = f"{arch}|{shape_name}|{mesh_name}" + (f"#{variant}" if variant
                                                else "")
    path = _hlo_path(hlo_dir, key)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with gzip.open(path, "wt") as f:
        f.write(f"# n_chips={n_chips} model_flops={mf}\n")
        f.write(text)


def reterm(results: Dict[str, Any], hlo_dir: str) -> int:
    """Recompute roofline terms from cached HLO (no recompilation)."""
    import gzip
    from .roofline import RooflineTerms
    from .hlo_cost import analyze_hlo
    n = 0
    for key, rec in results.items():
        if rec.get("status") != "ok":
            continue
        path = _hlo_path(hlo_dir, key)
        if not os.path.exists(path):
            continue
        with gzip.open(path, "rt") as f:
            hdr = f.readline()
            text = f.read()
        meta = dict(kv.split("=") for kv in hdr[1:].split())
        cost = analyze_hlo(text)
        from .roofline import CollectiveStats
        stats = CollectiveStats(
            bytes_by_kind=dict(cost.coll_bytes),
            count_by_kind={k: int(v) for k, v in cost.coll_count.items()})
        terms = RooflineTerms(
            flops=cost.flops, hbm_bytes=cost.bytes,
            collective_bytes=cost.total_coll_bytes,
            n_chips=int(meta["n_chips"]),
            model_flops=float(meta["model_flops"]), collectives=stats)
        rec["roofline"] = terms.to_dict()
        n += 1
    return n


def load_results(path: str) -> Dict[str, Any]:
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {}


def save_results(path: str, results: Dict[str, Any]) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(results, f, indent=1)
    os.replace(tmp, path)


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None,
                    choices=[s.name for s in ALL_SHAPES] + [None])
    ap.add_argument("--mesh", default="both",
                    choices=("pod1", "pod2", "both"))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default=None, choices=list(VARIANTS))
    ap.add_argument("--reterm", action="store_true",
                    help="recompute roofline terms from cached HLO only")
    ap.add_argument("--out", default=os.path.abspath(RESULTS_PATH))
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = [s.name for s in ALL_SHAPES] if (args.all or args.shape is None) \
        else [args.shape]
    meshes = {"pod1": [False], "pod2": [True],
              "both": [False, True]}[args.mesh]

    results = load_results(args.out)
    hlo_dir = _hlo_dir(args.out)
    if args.reterm:
        n = reterm(results, hlo_dir)
        save_results(args.out, results)
        print(f"re-derived terms for {n} cells from cached HLO")
        return
    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for multi_pod in meshes:
                key = f"{arch}|{shape_name}|{'pod2' if multi_pod else 'pod1'}"
                if args.variant:
                    key += f"#{args.variant}"
                if key in results and not args.force \
                        and results[key].get("status") in ("ok", "skip"):
                    continue
                try:
                    results[key] = run_cell(arch, shape_name, multi_pod,
                                            variant=args.variant,
                                            hlo_dir=hlo_dir)
                except Exception as e:
                    failures += 1
                    results[key] = {"status": "error",
                                    "error": f"{type(e).__name__}: {e}"}
                    print(f"[{key}] FAILED: {e}", flush=True)
                    traceback.print_exc()
                save_results(args.out, results)
    ok = sum(1 for r in results.values() if r.get("status") == "ok")
    sk = sum(1 for r in results.values() if r.get("status") == "skip")
    er = sum(1 for r in results.values() if r.get("status") == "error")
    print(f"dry-run: {ok} ok, {sk} skip, {er} error", flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
