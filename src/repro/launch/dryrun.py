import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any jax import (jax locks the device
count at first init); 512 placeholder host devices back the production
meshes. Per cell this emits a JSON artifact with:
  - memory_analysis (proves the program fits per-device HBM)
  - cost_analysis   (FLOPs / bytes; per-device, post-partitioning)
  - collective op schedule + byte counts (parsed from compiled HLO)
Probe variants (--probe 1|2) compile reduced-depth UNROLLED programs used by
the roofline to recover true per-layer costs (scan bodies are counted once by
HLO cost analysis — see DESIGN.md §6).

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k [--multipod]
         [--probe 0|1|2] [--kv-mode auto|head|seq] [--out artifacts/...]
  python -m repro.launch.dryrun --all [--multipod]
"""
import argparse
import json
import sys
import traceback
from pathlib import Path


def _probe_cfg(cfg, n_units: int):
    """Reduce depth to n_units 'repeating units' (layers, or zamba periods)."""
    if cfg.ssm is not None:
        return cfg.with_(n_layers=n_units * cfg.ssm.attn_every)
    return cfg.with_(n_layers=n_units)


def _probe_shape(cfg, shape):
    """Cap probe sequence length for chunked-recurrence archs (rwkv) whose
    unrolled chunk loops would blow up HLO size; costs are linear in S and
    are rescaled by the roofline (field ``probe_seq_scale``)."""
    import dataclasses
    if shape.kind == "decode":
        return shape, 1.0
    # rwkv is strictly token-linear (attention-free) -> exact rescale.
    # zamba: capped at 8192 for compile-time reasons; the (1/attn_every of
    # layers) shared-attention quadratic component is underestimated by the
    # linear rescale — noted in EXPERIMENTS.md §Roofline.
    cap = 4096 if cfg.rwkv is not None else (8192 if cfg.ssm is not None
                                             else None)
    if cap and shape.seq_len > cap:
        scale = shape.seq_len / cap
        return dataclasses.replace(shape, seq_len=cap), scale
    return shape, 1.0


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             probe: int = 0, kv_mode: str = "auto", seq_shard: bool = True,
             serve_fsdp: bool = False, variant: str = "",
             out_dir: str = "artifacts/dryrun", clock=None) -> dict:
    import jax
    from repro.configs import get_config, SHAPES, cell_is_supported
    from repro.distributed.sharding import activation_sharding
    from jax.sharding import set_mesh
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import make_step_and_specs
    from repro.roofline.hlo_parse import collective_summary
    from repro.serving.telemetry import MonotonicClock

    # lower_s/compile_s read the injected clock (telemetry Clock protocol);
    # real wall time by default, FakeClock under test
    clock = clock if clock is not None else MonotonicClock()

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell = f"{arch}__{shape_name}__{mesh_name}" + (f"__probe{probe}" if probe else "")
    if variant:
        cell += f"__{variant}"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "probe": probe, "kv_mode": kv_mode, "variant": variant,
           "serve_fsdp": serve_fsdp, "ok": False}

    ok, reason = cell_is_supported(cfg, shape)
    if not ok:
        rec.update(skipped=True, reason=reason, ok=True)
        return _save(rec, cell, out_dir)

    probe_scale = 1.0
    if probe:
        cfg = _probe_cfg(cfg, probe)
        shape, probe_scale = _probe_shape(cfg, shape)
    rec["probe_seq_scale"] = probe_scale
    rec["n_layers_used"] = cfg.n_layers

    t0 = clock.now()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        with set_mesh(mesh):
            jf, args, act_spec = make_step_and_specs(
                cfg, mesh, shape, unroll=bool(probe), kv_mode=kv_mode,
                seq_shard=seq_shard, serve_fsdp=serve_fsdp)
            with activation_sharding(act_spec):
                lowered = jf.lower(*args)
            t1 = clock.now()
            compiled = lowered.compile()
            t2 = clock.now()
        mem = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(mem, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes",
             "alias_size_in_bytes")
            if hasattr(mem, k)}
        ca = compiled.cost_analysis() or {}
        rec["cost"] = {k: float(v) for k, v in ca.items()
                       if isinstance(v, (int, float)) and
                       k in ("flops", "bytes accessed", "transcendentals",
                             "utilization operand", "bytes accessed output")}
        rec["cost"].setdefault("flops", float(ca.get("flops", 0.0)))
        hlo = compiled.as_text()
        rec["collectives"] = collective_summary(hlo)
        rec["hlo_bytes"] = len(hlo)
        rec["lower_s"] = round(t1 - t0, 2)
        rec["compile_s"] = round(t2 - t1, 2)
        rec["n_devices"] = mesh.size
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001 — record the failure, don't crash the sweep
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return _save(rec, cell, out_dir)


def _save(rec: dict, cell: str, out_dir: str) -> dict:
    p = Path(out_dir)
    p.mkdir(parents=True, exist_ok=True)
    (p / f"{cell}.json").write_text(json.dumps(rec, indent=1))
    status = "OK" if rec.get("ok") else "FAIL"
    if rec.get("skipped"):
        status = "SKIP"
    print(f"[dryrun] {cell}: {status}"
          + (f" compile={rec.get('compile_s')}s" if rec.get("ok") and not rec.get("skipped") else "")
          + (f" reason={rec.get('reason', rec.get('error', ''))[:120]}"
             if status != "OK" else ""))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--probe", type=int, default=0)
    ap.add_argument("--kv-mode", default="auto")
    ap.add_argument("--no-seq-shard", action="store_true")
    ap.add_argument("--serve-fsdp", action="store_true",
                    help="legacy: FSDP-shard weights in serving too "
                         "(the pre-i1 baseline)")
    ap.add_argument("--variant", default="",
                    help="artifact suffix for perf-iteration records")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args(argv)

    if args.all:
        from repro.configs import ASSIGNED_ARCHS, SHAPES
        rc = 0
        for arch in ASSIGNED_ARCHS:
            for shape in SHAPES:
                r = run_cell(arch, shape, multi_pod=args.multipod,
                             kv_mode=args.kv_mode, out_dir=args.out)
                rc |= 0 if r.get("ok") else 1
        sys.exit(rc)

    r = run_cell(args.arch, args.shape, multi_pod=args.multipod,
                 probe=args.probe, kv_mode=args.kv_mode,
                 seq_shard=not args.no_seq_shard,
                 serve_fsdp=args.serve_fsdp, variant=args.variant,
                 out_dir=args.out)
    sys.exit(0 if r.get("ok") else 1)


if __name__ == "__main__":
    main()
