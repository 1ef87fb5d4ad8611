import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes, print memory/cost analysis, dump JSON for the roofline.

Usage::

    python -m repro.launch.dryrun --arch yi-9b --shape train_4k [--multi-pod]
    python -m repro.launch.dryrun --grid [--out results/dryrun]

The grid mode runs each cell in a subprocess (isolation + timeout); a cell
failure never poisons the rest.  The FIRST TWO LINES of this file set
XLA_FLAGS before any jax import — jax locks the device count on first init.
(No ``from __future__`` import here for that same reason: nothing may
precede the XLA_FLAGS lines.)
"""
import argparse
import json
import re
import subprocess
import sys
import time

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_DT_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
             "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
             "f64": 8, "c64": 8, "c128": 16}


def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in the (post-SPMD,
    per-device) HLO. Returns per-op-kind byte totals."""
    out = {k: 0 for k in COLLECTIVES}
    counts = {k: 0 for k in COLLECTIVES}
    pat = re.compile(
        r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^=]*?\s("
        + "|".join(COLLECTIVES) + r")(?:-start|-done)?\(")
    for m in pat.finditer(hlo_text):
        dt, dims, op = m.group(1), m.group(2), m.group(3)
        if dt not in _DT_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[op] += n * _DT_BYTES[dt]
        counts[op] += 1
    out["counts"] = counts
    return out


def shard_bytes(struct_tree, sharding_tree) -> float:
    """Exact per-device bytes of a sharded pytree of ShapeDtypeStructs."""
    import jax
    import numpy as np

    total = 0.0
    for s, sh in zip(jax.tree.leaves(struct_tree),
                     jax.tree.leaves(sharding_tree,
                                     is_leaf=lambda x: hasattr(x, "spec"))):
        shards = 1
        mesh_axes = dict(zip(sh.mesh.axis_names, sh.mesh.devices.shape))
        for axis in jax.tree.leaves(tuple(sh.spec)):
            if axis is not None:
                shards *= mesh_axes[axis]
        total += np.prod(s.shape) * s.dtype.itemsize / max(shards, 1)
    return float(total)


def build_cell(arch: str, shape_name: str, multi_pod: bool):
    """Returns (jitted_fn, example_args_structs) for one cell."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..configs import SHAPES, get_config
    from ..configs.base import TrainConfig
    from ..models import decode_step, loss_fn, prefill
    from ..parallel.sharding import (
        batch_specs, cache_specs, dp_axes, params_shardings, to_shardings,
    )
    from ..train.train_step import init_state, make_train_step
    from .input_specs import cache_structs, input_specs, param_structs
    from .mesh import make_production_mesh

    cfg = get_config(arch)
    if os.environ.get("DRYRUN_PARAM_DTYPE"):
        # §Perf memory-fit knob: bf16 params + fp32 moments
        cfg = cfg.replace(param_dtype=os.environ["DRYRUN_PARAM_DTYPE"])
    sh = SHAPES[shape_name]
    split = os.environ.get("DRYRUN_MESH")  # e.g. "64x4": §Perf re-splits
    if split:
        d, m = (int(x) for x in split.split("x"))
        mesh = jax.make_mesh((d, m), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    rep = NamedSharding(mesh, P())

    p_struct = param_structs(cfg)
    batch = input_specs(arch, shape_name)
    b_specs = to_shardings(
        {k: v for k, v in batch_specs(
            cfg, mesh, sh.kind, sh.global_batch, sh.seq_len).items()
         if k in batch}, mesh)

    if sh.kind == "train":
        # §Perf knobs, settable without re-plumbing the grid runner
        tc = TrainConfig(
            grad_wire_dtype=os.environ.get("DRYRUN_GRAD_WIRE", "float32"),
            grad_compression=bool(os.environ.get("DRYRUN_GRAD_COMPRESS")),
        )
        state_struct = jax.eval_shape(lambda p: init_state(p, tc), p_struct)
        state_sh = params_shardings(state_struct, mesh)
        step = make_train_step(cfg, tc)
        metrics_struct = jax.eval_shape(
            lambda s, b: step(s, b)[1], state_struct, batch)
        metrics_sh = jax.tree.map(lambda _: rep, metrics_struct)
        fn = jax.jit(step, in_shardings=(state_sh, b_specs),
                     out_shardings=(state_sh, metrics_sh))
        args = (state_struct, batch)
        extra_bytes = shard_bytes(state_struct, state_sh)
    elif sh.kind == "prefill":
        p_sh = params_shardings(p_struct, mesh)

        def step(params, batch):
            logits, caches, ln, cross = prefill(
                params, cfg, batch["tokens"], max_len=sh.seq_len,
                frames=batch.get("frames"))
            return logits, caches

        fn = jax.jit(step, in_shardings=(p_sh, b_specs))
        args = (p_struct, batch)
        extra_bytes = shard_bytes(p_struct, p_sh)
    else:  # decode
        p_sh = params_shardings(p_struct, mesh)
        # sliding-window archs only ever attend to the last `window`
        # positions: a rolling cache bounds decode memory (§Perf)
        cache_len = sh.seq_len
        if cfg.sliding_window and os.environ.get("DRYRUN_SWA_CACHE"):
            cache_len = min(cache_len, cfg.sliding_window)
        caches = cache_structs(cfg, sh.global_batch, cache_len)
        c_specs = to_shardings(
            cache_specs(cfg, mesh, sh.global_batch, cache_len), mesh)
        length = jax.ShapeDtypeStruct((), jax.numpy.int32)
        cross = None
        cross_sh = None
        if cfg.is_encdec:
            cross = jax.ShapeDtypeStruct(
                (sh.global_batch, cfg.enc_seq, cfg.d_model),
                jax.numpy.float32)
            cross_sh = NamedSharding(
                mesh, P(dp_axes(mesh), None, None))

        def step(params, caches, tokens, length, cross_kv):
            return decode_step(params, cfg, tokens, caches, length,
                               cross_kv=cross_kv)

        fn = jax.jit(step, in_shardings=(
            p_sh, c_specs, b_specs["tokens"], rep, cross_sh))
        args = (p_struct, caches, batch["tokens"], length, cross)
        extra_bytes = (shard_bytes(p_struct, p_sh)
                       + shard_bytes(caches, c_specs))
    return fn, args, extra_bytes, mesh


def run_cell(arch: str, shape_name: str, multi_pod: bool) -> dict:
    import jax

    t0 = time.time()
    fn, args, arg_bytes, mesh = build_cell(arch, shape_name, multi_pod)
    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not implement it
        mem_d = {"error": str(e)}
    mem_d["sharded_argument_bytes_exact"] = arg_bytes

    text = compiled.as_text()
    coll = collective_bytes(text)
    hlo_path = os.environ.get("DRYRUN_HLO_PATH")
    if hlo_path:
        import gzip
        with gzip.open(hlo_path, "wt") as f:
            f.write(text)

    res = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": 512 if multi_pod else 256,
        "ok": True,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops_per_device": cost.get("flops"),
        "bytes_per_device": cost.get("bytes accessed"),
        "transcendentals": cost.get("transcendentals"),
        "memory": mem_d,
        "collectives": coll,
        "hlo_ops": text.count("\n"),
    }
    print(json.dumps(res))
    print("memory_analysis:", mem_d, file=sys.stderr)
    print("cost_analysis: flops=%s bytes=%s" % (
        cost.get("flops"), cost.get("bytes accessed")), file=sys.stderr)
    return res


def run_grid(out_dir: str, timeout: int, only: str | None = None,
             meshes: tuple = (False, True)) -> None:
    from ..configs import REGISTRY, shape_cells

    os.makedirs(out_dir, exist_ok=True)
    cells = []
    for arch in REGISTRY:
        for shape in shape_cells(arch):
            for mp in meshes:
                cells.append((arch, shape, mp))
    for arch, shape, mp in cells:
        if only and only not in arch:
            continue
        tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}"
        path = os.path.join(out_dir, tag + ".json")
        if os.path.exists(path):
            print("skip (done):", tag)
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape]
        if mp:
            cmd.append("--multi-pod")
        env = dict(os.environ)
        if not mp:  # keep HLO for the single-pod roofline analysis
            env["DRYRUN_HLO_PATH"] = os.path.join(out_dir, tag + ".hlo.gz")
        print("run:", tag, flush=True)
        try:
            p = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=timeout, env=env)
            line = [l for l in p.stdout.splitlines() if l.startswith("{")]
            if p.returncode == 0 and line:
                with open(path, "w") as f:
                    f.write(line[-1])
                print("  ok", flush=True)
            else:
                with open(path + ".err", "w") as f:
                    f.write(p.stdout[-4000:] + "\n---\n" + p.stderr[-6000:])
                print("  FAIL (see .err)", flush=True)
        except subprocess.TimeoutExpired:
            with open(path + ".err", "w") as f:
                f.write("timeout")
            print("  TIMEOUT", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--grid", action="store_true")
    ap.add_argument("--only")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--timeout", type=int, default=1800)
    args = ap.parse_args()
    if args.grid:
        run_grid(args.out, args.timeout, args.only)
    else:
        run_cell(args.arch, args.shape, args.multi_pod)


if __name__ == "__main__":
    main()
