import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede every other import (jax locks the device count on first
#   init). Only this launcher sees 512 placeholder devices; tests and
#   benchmarks run on the single real CPU device.

import argparse          # noqa: E402
import json              # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_IDS, ALIASES, get_config          # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_info    # noqa: E402
from repro.launch import specs as specs_lib                      # noqa: E402
from repro.parallel.sharding import (                            # noqa: E402
    param_shardings, batch_shardings, dp_axes, set_activation_mesh)
from repro.roofline.hlo_parse import collective_bytes            # noqa: E402
from repro.roofline.analysis import roofline_terms, model_flops  # noqa: E402

CANON = {v: k for k, v in ALIASES.items()}


def _rep(mesh):
    return NamedSharding(mesh, P())


def _cache_sharding(mesh, leaf):
    """Heuristic cache specs (see launch/specs.py docstring):
    [.., B, L, H, D] KV caches: L over 'data' when batch can't shard, heads
    over 'model'; small recurrent states: heads over 'model'."""
    dp = dp_axes(mesh)
    data = mesh.shape.get("data", 1)
    model = mesh.shape.get("model", 1)
    dp_total = 1
    for a in dp:
        dp_total *= mesh.shape.get(a, 1)
    shape = leaf.shape
    nd = len(shape)
    spec = [None] * nd
    # possible stacked leading dim (n_units): treat dims after it
    off = 1 if nd >= 5 else 0
    bdim = off
    if nd - off >= 2:
        if shape[bdim] % dp_total == 0 and shape[bdim] >= dp_total:
            spec[bdim] = dp
        elif nd - off >= 3 and shape[bdim + 1] % data == 0 and shape[bdim + 1] >= 4096:
            spec[bdim + 1] = "data"     # seq-sharded long cache (SP decode)
        # heads/latent dim over model
        hdim = bdim + 2 if nd - off >= 4 else bdim + 1
        if hdim < nd and spec[hdim] is None and shape[hdim] % model == 0 \
                and shape[hdim] >= model:
            spec[hdim] = "model"
        elif (nd - off >= 4 and spec[bdim + 1] is None
              and shape[bdim + 1] % model == 0 and shape[bdim + 1] >= 4096):
            # heads unshardable (whisper kv=20, granite kv=1): shard cache
            # LENGTH over 'model' instead (sequence-parallel decode)
            spec[bdim + 1] = "model"
    return NamedSharding(mesh, P(*spec))


def _tree_sharding(mesh, tree, fn):
    return jax.tree.map(lambda l: fn(mesh, l), tree)


def build_shardings(mesh, kind, args, model_cfg, exclude_vocab_fsdp=False):
    """in_shardings matching build_cell's abstract args."""
    ev = exclude_vocab_fsdp
    if kind == "train":
        state, batch = args
        p_sh = param_shardings(state.params, mesh, exclude_vocab_fsdp=ev)
        from repro.optim.optimizer import AdamWState
        opt_sh = AdamWState(
            mu=param_shardings(state.opt_state.mu, mesh, exclude_vocab_fsdp=ev),
            nu=param_shardings(state.opt_state.nu, mesh, exclude_vocab_fsdp=ev),
            count=_rep(mesh))
        mon_sh = jax.tree.map(lambda _: _rep(mesh), state.monitors) \
            if state.monitors is not None else None
        qc_sh = jax.tree.map(lambda _: _rep(mesh), state.qclip) \
            if state.qclip is not None else None
        state_sh = type(state)(params=p_sh, opt_state=opt_sh, step=_rep(mesh),
                               rng=_rep(mesh), monitors=mon_sh, qclip=qc_sh)
        return (state_sh, batch_shardings(batch, mesh))
    if kind == "prefill":
        params, batch = args
        return (param_shardings(params, mesh, exclude_vocab_fsdp=ev),
                batch_shardings(batch, mesh))
    # decode
    params = args[0]
    p_sh = param_shardings(params, mesh, exclude_vocab_fsdp=ev)
    tok_sh = _rep(mesh)  # [B, 1] tiny; replicating avoids 1-wide dp shards
    cache_sh = _tree_sharding(mesh, args[2], _cache_sharding)
    out = [p_sh, tok_sh, cache_sh, _rep(mesh)]
    if len(args) == 5:   # encdec memory
        out.append(batch_shardings(args[4], mesh))
    return tuple(out)


def _compile_and_measure(arch, shape, mesh, kind, overrides=None,
                         want_memory=True, want_hlo=True, variant="baseline"):
    """One lower+compile; returns measurement dict."""
    out = {}
    ov = dict(specs_lib.VARIANTS.get(variant, {}))
    exclude_vocab = bool(ov.pop("exclude_vocab_fsdp", False))
    ov.update(overrides or {})
    fn, args, donate = specs_lib.build_cell(arch, shape, ov or None)
    cfg_used = get_config(arch)
    in_sh = build_shardings(mesh, kind, args, cfg_used,
                            exclude_vocab_fsdp=exclude_vocab)
    with mesh:
        jitted = jax.jit(fn, in_shardings=in_sh, donate_argnums=donate)
        t1 = time.time()
        lowered = jitted.lower(*args)
        t2 = time.time()
        compiled = lowered.compile()
        t3 = time.time()
    out["lower_s"] = round(t2 - t1, 2)
    out["compile_s"] = round(t3 - t2, 2)
    try:
        cost = compiled.cost_analysis()
    except Exception as e:  # pragma: no cover
        cost, out["cost_error"] = {}, str(e)
    out["flops"] = float(cost.get("flops", 0.0))
    out["bytes"] = float(cost.get("bytes accessed", 0.0))
    if want_memory:
        try:
            ma = compiled.memory_analysis()
            out["memory_analysis"] = {
                k: int(getattr(ma, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes",
                          "alias_size_in_bytes")
                if hasattr(ma, k)
            }
        except Exception as e:  # pragma: no cover
            out["memory_analysis_error"] = str(e)
    if want_hlo:
        total_coll, by_op, counts = collective_bytes(compiled.as_text())
        out["collective_bytes"] = total_coll
        out["collective_by_op"] = by_op
        out["collective_counts"] = counts
    return out


def _n_units(cfg) -> int:
    if cfg.is_encdec:
        return cfg.enc_layers  # enc & dec scale together in the probes
    if cfg.layer_pattern:
        return cfg.num_layers // len(cfg.layer_pattern)
    if cfg.window_pattern:
        return cfg.num_layers // len(cfg.window_pattern)
    return cfg.num_layers - cfg.moe_first_dense


def run_cell(arch: str, shape: str, mesh_kind: str, outdir: str,
             variant: str = "baseline", skip_probes: bool = False) -> dict:
    t0 = time.time()
    arch_canon = CANON.get(arch, arch)
    rec = {"arch": arch_canon, "shape": shape, "mesh": mesh_kind,
           "variant": variant, "ok": False}
    supported, why = specs_lib.cell_supported(arch_canon, shape)
    if not supported:
        rec.update(skipped=True, reason=why, ok=True)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec["mesh_info"] = mesh_info(mesh)
    cfg = get_config(arch_canon)
    kind = specs_lib.SHAPES[shape]["kind"]
    set_activation_mesh(mesh)
    try:
        # ---- A: the PRODUCTION lowering (scan-stacked, chunked attention) —
        # this is the multi-pod coherence + memory proof.
        prod = _compile_and_measure(arch_canon, shape, mesh, kind,
                                    variant=variant)
        rec["production"] = prod

        # ---- B/C: shallow UNROLLED probes for exact per-layer costs
        # (XLA cost_analysis counts while-loop bodies once; probes have
        #  trip-count-1 loops, costs extrapolate linearly in depth).
        # FLOPs probes use one-chunk attention (exact compute; the S-squared
        # score tensor is symbolic only). Collective probes use PRODUCTION
        # chunking: the chunked kv scans contain no collectives, so per-layer
        # collective bytes are exact, without the score-tensor resharding
        # artifacts the one-chunk form introduces.
        n = _n_units(cfg)

        def extrap(x2, x1):
            per_unit = max(x2 - x1, 0.0)
            return x2 + (n - 2) * per_unit

        if skip_probes:
            dev_flops = prod["flops"]
            dev_coll = prod["collective_bytes"]
            dataflow_bytes = prod["bytes"]
            by_op = prod["collective_by_op"]
        else:
            f2 = _compile_and_measure(
                arch_canon, shape, mesh, kind,
                overrides=specs_lib.probe_overrides(cfg, shape, 2, one_chunk=True),
                want_memory=False, variant=variant)
            f1 = _compile_and_measure(
                arch_canon, shape, mesh, kind,
                overrides=specs_lib.probe_overrides(cfg, shape, 1, one_chunk=True),
                want_memory=False, variant=variant)
            c2 = _compile_and_measure(
                arch_canon, shape, mesh, kind,
                overrides=specs_lib.probe_overrides(cfg, shape, 2, one_chunk=False),
                want_memory=False, variant=variant)
            c1 = _compile_and_measure(
                arch_canon, shape, mesh, kind,
                overrides=specs_lib.probe_overrides(cfg, shape, 1, one_chunk=False),
                want_memory=False, variant=variant)
            rec["probe_flops"] = {"p2": f2["flops"], "p1": f1["flops"],
                                  "compile_s": f2["compile_s"] + f1["compile_s"]}
            rec["probe_coll"] = {"p2": c2["collective_bytes"],
                                 "p1": c1["collective_bytes"],
                                 "compile_s": c2["compile_s"] + c1["compile_s"]}
            dev_flops = extrap(f2["flops"], f1["flops"])
            dev_coll = extrap(c2["collective_bytes"], c1["collective_bytes"])
            dataflow_bytes = extrap(c2["bytes"], c1["bytes"])
            by_op = {
                op: extrap(c2["collective_by_op"].get(op, 0),
                           c1["collective_by_op"].get(op, 0))
                for op in set(c2["collective_by_op"]) | set(c1["collective_by_op"])
            }

        # memory term: analytic HBM model (XLA 'bytes accessed' counts VMEM-
        # resident flash tiles as traffic; kept as dataflow diagnostic)
        from repro.roofline.analysis import analytic_hbm_bytes
        import dataclasses as _dc
        _fields = {f.name for f in _dc.fields(cfg)}
        _vov = {k: v for k, v in specs_lib.VARIANTS.get(variant, {}).items()
                if k in _fields}
        cfg_v = _dc.replace(cfg, **_vov) if _vov else cfg
        pshape = specs_lib.SHAPES[shape]
        dp_total = mesh.size // mesh.shape.get("model", 1)
        dev_bytes = analytic_hbm_bytes(cfg_v, kind, pshape["batch"], pshape["seq"],
                                       dp=dp_total,
                                       model=mesh.shape.get("model", 1))

        rec["device_flops"] = dev_flops
        rec["device_bytes"] = dev_bytes
        rec["device_dataflow_bytes"] = dataflow_bytes
        rec["device_collective_bytes"] = dev_coll
        rec["collective_by_op"] = by_op
        rec["n_units"] = n

        tokens = (specs_lib.SHAPES[shape]["batch"] *
                  (1 if kind == "decode" else specs_lib.SHAPES[shape]["seq"]))
        mf = model_flops(cfg, tokens, kind)
        from repro.roofline.analysis import hw_for
        terms = roofline_terms(dev_flops, dev_bytes, dev_coll,
                               hw=hw_for("tpu-v5e"),  # the assignment's target part
                               model_flops_global=mf, n_chips=mesh.size,
                               links=4)
        rec["roofline"] = terms
        rec["tokens_per_step"] = tokens
        rec["n_params"] = cfg.n_params()
        rec["n_active_params"] = cfg.n_active_params()
        rec["ok"] = True
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    finally:
        set_activation_mesh(None)
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(specs_lib.SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--all", action="store_true",
                    help="sweep every (arch, shape, mesh) in subprocesses")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--skip-probes", action="store_true",
                    help="production compile only (multi-pod coherence proof;"
                         " roofline probes are single-pod per the spec)")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)

    if args.all:
        cells = [(a, s, m)
                 for a in ARCH_IDS
                 for s in specs_lib.SHAPES
                 for m in ("single", "multi")]
        for a, s, m in cells:
            fname = os.path.join(args.out, f"{a}__{s}__{m}.json")
            if os.path.exists(fname) and not args.force:
                print(f"skip (exists): {fname}")
                continue
            print(f"=== {a} {s} {m}", flush=True)
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", a, "--shape", s, "--mesh", m,
                   "--out", args.out]
            if m == "multi":
                cmd.append("--skip-probes")
            env = dict(os.environ)
            env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
            r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                               timeout=3600)
            if r.returncode != 0:
                rec = {"arch": CANON.get(a, a), "shape": s, "mesh": m,
                       "ok": False,
                       "error": f"subprocess rc={r.returncode}",
                       "stderr": r.stderr[-3000:]}
                with open(fname, "w") as f:
                    json.dump(rec, f, indent=1)
                print(f"    FAILED rc={r.returncode}", flush=True)
            else:
                print("    done", flush=True)
        return

    rec = run_cell(args.arch, args.shape, args.mesh, args.out,
                   variant=args.variant, skip_probes=args.skip_probes)
    # filenames keyed by module arch id, aligned with the --all driver
    suffix = "" if args.variant == "baseline" else f"__{args.variant}"
    fname = os.path.join(
        args.out, f"{args.arch}__{args.shape}__{args.mesh}{suffix}.json")
    with open(fname, "w") as f:
        json.dump(rec, f, indent=1)
    status = "OK" if rec.get("ok") else "FAIL"
    if rec.get("skipped"):
        status = "SKIP"
    print(f"[{status}] {args.arch} {args.shape} {args.mesh} "
          f"({rec.get('total_s', 0)}s)")
    if not rec.get("ok"):
        print(rec.get("error", ""))
        print(rec.get("traceback", "")[-2000:])
        sys.exit(1)
    if "roofline" in rec:
        t = rec["roofline"]
        print(json.dumps({k: t[k] for k in
                          ("compute_s", "memory_s", "collective_s", "bound")},
                         indent=1))


if __name__ == "__main__":
    main()
