"""Chip smoke test: the MLSL training step on TPU at Yi-6B's published widths.

Drives `repro.launch.train.run` -- the training CLI's own setup and loop --
on Yi-6B (d 4096, 32 q / 4 kv heads x 128, ff 11008, vocab 64000) cut to 2
layers, with random weights and synthetic batches from --seed, and checks
what comes out:

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # the four-chip host

One chip: the MLSL step with the int8 wire and error feedback (the
compiled quant8 kernels), then, as the reference, the GSPMD step with the
fp32 wire on the same weights and batches. Four chips: two-level data
parallelism on a (node 2, local 2) mesh -- bf16 intra-node leg, int8 with
error feedback on the fabric leg -- against GSPMD fp32 data parallelism over
the same four chips.

Checks: each variant compiles once, every loss and grad norm is finite, the
int8-wire step carries Mosaic kernels (tpu_custom_call), the first-step
losses of system and reference agree within LOSS_RTOL (their forward passes
are the same), and on four chips the mesh, the batch and the state span
all four devices. Exits 1 without a result line when JAX finds no TPU or a
check fails; a passing run ends with one JSON line naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import statistics
import sys

SEQ = 2048
STEPS = 5
# Global batch (rows of SEQ tokens). One chip holds 1 row: with the f32
# error-feedback residuals (3.5 GB at dp=1) a 2-row step compiles to
# 16.94 GB against 16.9 GB of usable HBM, most of the difference being the
# f32 logits over the 64000-word vocabulary. Four chips hold 2 rows each.
BATCH = {1: 1, 4: 8}
# first-step losses of system and reference: the same forward pass, compiled
# into two programs whose bf16 arithmetic may associate differently
LOSS_RTOL = 1e-2

VARIANTS = {
    1: (("mlsl_int8_ef", ["--comm", "mlsl", "--wire", "int8",
                          "--error-feedback"]),
        ("gspmd_fp32", ["--comm", "gspmd", "--wire", "fp32"])),
    4: (("mlsl_hier_int8_ef", ["--comm", "mlsl", "--hier", "--nodes", "2",
                               "--local", "2", "--wire", "int8",
                               "--wire-intra", "bf16", "--error-feedback"]),
        ("gspmd_fp32_dp4", ["--comm", "gspmd", "--wire", "fp32",
                            "--data-parallel", "4"])),
}


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def model_config():
    from repro.configs import registry
    return dataclasses.replace(registry.get_config("yi-6b"), n_layers=2)


def peak_bytes(devices) -> int:
    """Most HBM any of `devices` has held so far in this process."""
    return max(d.memory_stats()["peak_bytes_in_use"] for d in devices)


def run_variant(train, cfg, name: str, flags: list, chips: int, seed: int):
    import jax

    args = train.build_parser().parse_args(
        flags + ["--batch", str(BATCH[chips]), "--seq", str(SEQ),
                 "--steps", str(STEPS), "--log-every", "1",
                 "--seed", str(seed)])
    res = train.run(cfg, args)
    steps, losses, gnorms, ts = zip(*res.history)
    check(list(steps) == list(range(STEPS)), f"{name}: logged {steps}")
    devices = sorted(res.state.step.sharding.device_set, key=lambda d: d.id)
    ma = res.compiled.memory_analysis()
    out = {
        "variant": name,
        "compile_s": res.compile_s,
        "compiles": res.n_compiles,
        "step_ms": [(b - a) * 1e3 for a, b in zip(ts, ts[1:])],
        "losses": list(losses),
        "grad_norms": list(gnorms),
        "compiled_bytes": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                           + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
        "tpu_custom_call": "tpu_custom_call" in res.compiled.as_text(),
        "peak_bytes_in_use": peak_bytes(devices),
        "devices": [d.id for d in devices],
    }
    out["step_ms_median"] = statistics.median(out["step_ms"])
    check(res.n_compiles == 1, f"{name}: {res.n_compiles} compiles")
    check(all(math.isfinite(v) for v in losses + gnorms),
          f"{name}: non-finite loss or grad norm {losses} {gnorms}")
    if chips == 4:
        check(len(devices) == 4, f"{name}: mesh on devices {out['devices']}")
        tokens = res.compiled.input_shardings[0][1].tokens
        check(len(tokens.device_set) == 4
              and tokens.shard_shape((BATCH[4], SEQ))[0] == BATCH[4] // 4,
              f"{name}: batch not split over 4 chips: {tokens}")
        for x in jax.tree_util.tree_leaves(res.state):
            check(x.sharding.device_set == set(devices),
                  f"{name}: state leaf on {x.sharding.device_set}")
    # free this variant's state before the next one is built
    for x in jax.tree_util.tree_leaves(res.state):
        x.delete()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=sorted(VARIANTS), default=1)
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < opts.chips:
        print(f"chip_smoke: --chips {opts.chips} but JAX found "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 1
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    try:
        from repro.launch import train
    except ImportError as e:
        print(f"chip_smoke: the repo's src/ is not beside this script: {e}",
              file=sys.stderr)
        return 1
    train.enable_compile_cache()
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"devices={len(jax.devices())} chips={opts.chips}", flush=True)

    cfg = model_config()
    results = []
    try:
        for name, flags in VARIANTS[opts.chips]:
            r = run_variant(train, cfg, name, flags, opts.chips, opts.seed)
            print(json.dumps(r), flush=True)
            results.append(r)
        system, reference = results
        check(system["tpu_custom_call"],
              f"{system['variant']}: no tpu_custom_call in the compiled step")
        l_sys, l_ref = system["losses"][0], reference["losses"][0]
        rel = abs(l_sys - l_ref) / abs(l_ref)
        print(f"first-step loss {system['variant']}={l_sys!r} "
              f"{reference['variant']}={l_ref!r} rel_diff={rel!r} "
              f"rtol={LOSS_RTOL}", flush=True)
        check(rel <= LOSS_RTOL, f"first-step losses differ by {rel:.3g}")
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
