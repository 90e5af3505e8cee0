"""Roofline-term extraction (one per assigned arch x shape x mesh).

Why not read FLOPs straight off the full train-step compile?  XLA's
``cost_analysis`` counts each ``while``-loop body ONCE, and our train step
nests three scans (tau local steps x grad-accum x layer blocks).  We
therefore decompose:

  outer_step_cost = tau*accum * C_micro  +  tau * C_base  +  C_global

  C_micro  — one microbatch value_and_grad (vmapped over workers).  The
             layer-block scan inside is handled by lowering the SAME model
             at L = plen and L = 2*plen layers and solving the linear model
             cost(L) = a + b*L  (exact: scan bodies are layer-homogeneous),
             then evaluating at the full layer count.
  C_base   — one base-optimizer update over the full stacked params
             (elementwise, no scans -> counted exactly).
  C_global — the paper's tau-amortized step: worker all-reduce + global
             sign momentum + re-broadcast (elementwise + collectives,
             no scans -> counted exactly).

Serve shapes (prefill/decode) have only the layer scan -> the two-point
layer extrapolation alone.  Peak HBM always comes from the FULL compile
(launch/dryrun.py), which is also the pass/fail deliverable.

Run:  PYTHONPATH=src python -m benchmarks.roofline --arch all --shape all
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import INPUT_SHAPES, ARCH_IDS, arch_supports_shape, load_arch
from repro.configs import specs as S
from repro.core import DSMConfig, get_base_optimizer
from repro.core import workers as WK
from repro.core.dsm import global_sign_momentum_step
from repro.distributed import sharding as shd
from repro.launch import dryrun as DR
from repro.launch.mesh import MODEL_PAR, make_production_mesh, serving_mesh, training_mesh
from repro.models import transformer as T


def _cost_of(lowered) -> dict:
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    coll = DR.collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "wire": float(coll["wire_bytes"]),
        "coll": coll,
    }


def _lin(c1: dict, c2: dict, l1: int, l2: int, l_full: int) -> dict:
    """cost(L) = a + b*L from two points, evaluated at l_full."""
    out = {}
    for k in ("flops", "bytes", "wire"):
        b = (c2[k] - c1[k]) / (l2 - l1)
        a = c1[k] - b * l1
        # clamp: tiny per-layer wire can extrapolate below zero when the
        # two-point costs are dominated by layer-independent terms
        out[k] = max(a + b * l_full, 0.0)
    return out


def _reduced(cfg, n_layers: int, enc_layers: int = None):
    kw = {"n_layers": n_layers}
    if cfg.family == "encdec":
        kw["enc_layers"] = enc_layers if enc_layers is not None else n_layers
    return dataclasses.replace(cfg, **kw)


# ---------------------------------------------------------------------------
# Train decomposition
# ---------------------------------------------------------------------------

def _train_micro_cost(cfg, topo, shape, mesh, W, zero, n_layers):
    """Lower one microbatch value_and_grad at a reduced layer count."""
    rcfg = _reduced(cfg, n_layers)
    rep = () if topo.attn_tp else ("wq", "wk", "wv", "wo")
    aps = S.abstract_params(rcfg)
    wparams = jax.eval_shape(lambda p: WK.broadcast_workers(p, W), aps)
    full = S.train_batch_specs(cfg, topo, shape, W)
    micro = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((W,) + l.shape[3:], l.dtype), full
    )

    wspec = shd.to_named(
        shd.param_pspecs(wparams, model=MODEL_PAR, zero=zero, worker_axis=True,
                         replicate_names=rep), mesh)
    mspec = shd.to_named(
        jax.tree.map(lambda l: P(*(("worker",) + (None,) * (len(l.shape) - 1))), micro,
                     is_leaf=lambda x: hasattr(x, "shape")), mesh)

    # unroll=True: layer costs must scale with L for the two-point fit
    # (XLA cost_analysis counts while bodies once)
    loss = lambda p, b: T.loss_fn(p, b, rcfg, remat=topo.remat, unroll=True,
                                  remat_policy=getattr(topo, "remat_policy", "full"))

    def micro_grad(params_w, mb):
        axes = WK.worker_axes(params_w)
        return jax.vmap(jax.value_and_grad(loss), in_axes=(axes, 0),
                        out_axes=(0, axes))(params_w, mb)

    out_sh = (NamedSharding(mesh, P("worker")), wspec)
    with mesh:
        lowered = jax.jit(
            micro_grad, in_shardings=(wspec, mspec), out_shardings=out_sh
        ).lower(wparams, micro)
    return _cost_of(lowered)


def _train_base_cost(cfg, topo, mesh, W, zero):
    """One base-optimizer direction+update over the FULL stacked params."""
    base_opt = get_base_optimizer(topo.base_opt)
    aps = S.abstract_params(cfg)
    wparams = jax.eval_shape(lambda p: WK.broadcast_workers(p, W), aps)
    bstate = jax.eval_shape(
        lambda p: WK.broadcast_workers(base_opt.init(p), W), aps)

    wspec = shd.to_named(
        shd.param_pspecs(wparams, model=MODEL_PAR, zero=zero, worker_axis=True), mesh)
    bspec = shd.to_named(
        shd.param_pspecs(bstate, model=MODEL_PAR, zero=zero, worker_axis=True), mesh)

    def base_step(params_w, grads_w, bs_w):
        def per_worker(p, g, bs):
            d, new_bs = base_opt.direction(g, bs, p, jnp.zeros((), jnp.int32))
            new_p = jax.tree.map(
                lambda x, dd: (x.astype(jnp.float32)
                               - 3e-4 * dd.astype(jnp.float32)).astype(x.dtype),
                p, d)
            return new_p, new_bs

        axes = (WK.worker_axes(params_w), WK.worker_axes(bs_w))
        return jax.vmap(per_worker, in_axes=(axes[0], *axes),
                        out_axes=axes)(params_w, grads_w, bs_w)

    with mesh:
        lowered = jax.jit(
            base_step, in_shardings=(wspec, wspec, bspec),
            out_shardings=(wspec, bspec),
        ).lower(wparams, wparams, bstate)
    return _cost_of(lowered)


def _train_global_cost(cfg, topo, mesh, W, zero, zero_global_buffers=False):
    """The paper's global step: all-reduce over workers + sign momentum + sync."""
    aps = S.abstract_params(cfg)
    wparams = jax.eval_shape(lambda p: WK.broadcast_workers(p, W), aps)
    m_sds = jax.eval_shape(
        lambda p: jax.tree.map(lambda l: jnp.zeros(l.shape, jnp.float32), p), aps)

    wspec = shd.to_named(
        shd.param_pspecs(wparams, model=MODEL_PAR, zero=zero, worker_axis=True), mesh)
    gz_axes = ("worker", "zero") if zero_global_buffers else ("zero",)
    gz = zero * (W if zero_global_buffers else 1)
    gspec = shd.to_named(
        shd.param_pspecs(aps, model=MODEL_PAR, zero=gz, zero_axes=gz_axes), mesh)
    mspec = shd.to_named(
        shd.param_pspecs(m_sds, model=MODEL_PAR, zero=gz, zero_axes=gz_axes), mesh)

    dsm_cfg = DSMConfig(tau=topo.tau)

    def gstep(x0, m, params_w):
        x_tau = WK.worker_mean(params_w)  # THE all-reduce
        new_x0, new_m = global_sign_momentum_step(
            x0, m, x_tau, jnp.float32(3e-4), dsm_cfg)
        return new_x0, new_m, WK.broadcast_workers(new_x0, W)

    with mesh:
        lowered = jax.jit(
            gstep, in_shardings=(gspec, mspec, wspec),
            out_shardings=(gspec, mspec, wspec),
        ).lower(aps, m_sds, wparams)
    return _cost_of(lowered)


def roofline_train(arch_id: str, shape_name: str, multi_pod: bool,
                   zero_global_buffers: bool = False, overrides: dict = None,
                   cfg_overrides: dict = None) -> dict:
    mod = load_arch(arch_id)
    cfg, topo = mod.FULL, mod.TOPO
    if overrides:
        topo = dataclasses.replace(topo, **{k: v for k, v in overrides.items()
                                            if hasattr(topo, k)})
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = INPUT_SHAPES[shape_name]
    base = make_production_mesh(multi_pod=multi_pod)
    W = topo.n_workers_multi if multi_pod else topo.n_workers_single
    mesh = training_mesh(base, W)
    zero = mesh.devices.shape[1]

    plen = len(cfg.pattern)
    c1 = _train_micro_cost(cfg, topo, shape, mesh, W, zero, plen)
    c2 = _train_micro_cost(cfg, topo, shape, mesh, W, zero, 2 * plen)
    micro = _lin(c1, c2, plen, 2 * plen, cfg.n_layers)
    basec = _train_base_cost(cfg, topo, mesh, W, zero)
    glob = _train_global_cost(cfg, topo, mesh, W, zero, zero_global_buffers)

    tau, acc = topo.tau, topo.grad_accum
    total = {
        k: tau * acc * micro[k] + tau * basec[k] + glob[k]
        for k in ("flops", "bytes", "wire")
    }
    tokens = shape.global_batch * shape.seq_len * tau  # per outer step
    model_flops = 6 * S.active_param_count(cfg) * tokens / mesh.devices.size

    return _terms(total, model_flops, mesh, arch_id, shape_name, multi_pod,
                  parts={"micro": micro, "base": basec, "global": glob,
                         "tau": tau, "accum": acc})


# ---------------------------------------------------------------------------
# Serve decomposition (layer extrapolation only)
# ---------------------------------------------------------------------------

def _serve_cost(arch_id, cfg, shape_name, mesh, multi_pod, n_layers):
    rcfg = _reduced(cfg, n_layers)
    kind = INPUT_SHAPES[shape_name].kind
    # rebuild with the reduced cfg via a patched arch module view
    import types

    mod = types.SimpleNamespace(FULL=rcfg, TOPO=load_arch(arch_id).TOPO)
    orig = DR.load_arch
    DR.load_arch = lambda a: mod  # scoped monkey-patch
    try:
        if kind == "prefill":
            lowered, _ = DR.build_prefill(arch_id, shape_name, multi_pod, unroll=True)
        else:
            lowered, _ = DR.build_decode(arch_id, shape_name, multi_pod, unroll=True)
    finally:
        DR.load_arch = orig
    return _cost_of(lowered)


def roofline_serve(arch_id: str, shape_name: str, multi_pod: bool) -> dict:
    mod = load_arch(arch_id)
    cfg = mod.FULL
    base = make_production_mesh(multi_pod=multi_pod)
    mesh = serving_mesh(base)
    plen = len(cfg.pattern)
    c1 = _serve_cost(arch_id, cfg, shape_name, mesh, multi_pod, plen)
    c2 = _serve_cost(arch_id, cfg, shape_name, mesh, multi_pod, 2 * plen)
    total = _lin(c1, c2, plen, 2 * plen, cfg.n_layers)

    shape = INPUT_SHAPES[shape_name]
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * S.active_param_count(cfg) * tokens / mesh.devices.size
    else:
        tokens = shape.global_batch
        model_flops = 2 * S.active_param_count(cfg) * tokens / mesh.devices.size
    return _terms(total, model_flops, mesh, arch_id, shape_name, multi_pod, parts={})


def _terms(total, model_flops, mesh, arch_id, shape_name, multi_pod, parts):
    t_c = total["flops"] / DR.PEAK_FLOPS
    t_m = total["bytes"] / DR.HBM_BW
    t_n = total["wire"] / DR.ICI_BW
    dom = max([("compute", t_c), ("memory", t_m), ("collective", t_n)],
              key=lambda kv: kv[1])[0]
    return {
        "arch": arch_id, "shape": shape_name,
        "mesh": "multipod" if multi_pod else "singlepod",
        "hlo_flops_per_chip": total["flops"],
        "hlo_bytes_per_chip": total["bytes"],
        "wire_bytes_per_chip": total["wire"],
        "t_compute_s": t_c, "t_memory_s": t_m, "t_collective_s": t_n,
        "dominant": dom,
        "model_flops_per_chip": model_flops,
        "useful_flops_ratio": model_flops / total["flops"] if total["flops"] else 0.0,
        "roofline_bound_s": max(t_c, t_m, t_n),
        "parts": parts,
        "n_chips": mesh.devices.size,
        "status": "ok",
    }


def run_one(arch_id, shape_name, multi_pod, outdir, **kw):
    t0 = time.time()
    try:
        if INPUT_SHAPES[shape_name].kind == "train":
            rec = roofline_train(arch_id, shape_name, multi_pod, **kw)
        else:
            rec = roofline_serve(arch_id, shape_name, multi_pod)
    except Exception as e:  # noqa: BLE001
        import traceback

        rec = {"arch": arch_id, "shape": shape_name,
               "mesh": "multipod" if multi_pod else "singlepod",
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-3000:]}
    rec["wall_s"] = round(time.time() - t0, 1)
    if outdir:
        os.makedirs(outdir, exist_ok=True)
        tag = f"{arch_id}.{shape_name}.{'multipod' if multi_pod else 'singlepod'}"
        with open(os.path.join(outdir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--outdir", default="experiments/roofline")
    ap.add_argument("--zero-global-buffers", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(INPUT_SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    for arch_id in archs:
        mod = load_arch(arch_id)
        for shape_name in shapes:
            if not arch_supports_shape(mod.FULL, mod.TOPO, shape_name):
                print(f"SKIP {arch_id} x {shape_name}")
                continue
            for mp in meshes:
                kw = {}
                if (INPUT_SHAPES[shape_name].kind == "train"
                        and args.zero_global_buffers):
                    kw["zero_global_buffers"] = True
                rec = run_one(arch_id, shape_name, mp, args.outdir, **kw)
                if rec["status"] == "ok":
                    print(f"OK  {arch_id:28s} {shape_name:12s} dom={rec['dominant']:10s} "
                          f"tc={rec['t_compute_s']:.3e} tm={rec['t_memory_s']:.3e} "
                          f"tn={rec['t_collective_s']:.3e} "
                          f"useful={rec['useful_flops_ratio']:.2f} ({rec['wall_s']}s)",
                          flush=True)
                else:
                    print(f"ERR {arch_id:28s} {shape_name:12s} {rec['error'][:180]}",
                          flush=True)


if __name__ == "__main__":
    main()
