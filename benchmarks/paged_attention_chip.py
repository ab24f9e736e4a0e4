"""Paged decode attention on the chip: the Pallas kernel against the
compiler's gather-then-attend (``ref.paged_decode_attention_ref``).

Each variant runs as one program of ``--calls`` chained calls, as the
layers of a decode step run them: each call first writes a token into the
pools at every live row's last slot (idle rows write a trash page that no
table names), and its output is the next call's query.  Under the
profiler, ``us_per_call`` is the median program time on the device over
the calls, and ``kernel_us`` the median device time of the operations
named ``paged_decode_attention`` per call (none for the gather).  Shapes:

- ``cell``: danube3-4b's serving step in the benchmark's one-chip cell:
  16 rows, GQA 32/8 at head 120, page 16, 5 pages per row, a bf16 pool of
  81 pages; a quarter of the rows idle, the rest at 15-70 tokens.
- ``phi3v``: the same rows at phi-3-vision's MHA widths, 32/32 at head 96.
- ``phi3v-cell``: the benchmark's phi3v16 cell: 96 rows at those widths
  over a 482-page pool, too large to read in place (streamed).
- ``long``: danube3 at 8 rows of up to 257 pages, sliding window 4096.

Also checks each variant against the float32 oracle on the rows with a
length (the kernel writes zeros for an idle row, the oracle an average).  Needs a TPU:

    PYTHONPATH=src python benchmarks/paged_attention_chip.py --out kernel.json
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import statistics
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import paged_attention as pa
from repro.kernels import ref

PAGE = 16
# (rows, heads, kv heads, head dim, pages per row, window)
SHAPES = {
    "cell": (16, 32, 8, 120, 5, 0),
    "phi3v": (16, 32, 32, 96, 5, 0),
    "phi3v-cell": (96, 32, 32, 96, 5, 0),
    "long": (8, 32, 8, 120, 4096 // PAGE + 1, 4096),
}
HBM_BYTES_PER_S = 819e9  # TPU v5e (Google Cloud, "TPU v5e")


def inputs(shape: str, seed: int):
    b, h, kv, d, maxp, window = SHAPES[shape]
    rng = np.random.default_rng(seed)
    idle = b // 4
    top = maxp * PAGE
    lens = np.concatenate([np.zeros(idle, int), rng.integers(top // 5, top + 1, b - idle)])
    rng.shuffle(lens)
    pool = b * maxp + 2  # the last page is the trash page
    table = rng.permutation(pool - 1)[: b * maxp].reshape(b, maxp)
    for i, n in enumerate(lens):  # entries past a row's pages: page 0 or a live page
        dead = np.arange(maxp) >= -(-n // PAGE)
        table[i, dead] = np.where(rng.random(dead.sum()) < 0.5, 0, table[(i + 1) % b, 0])
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, h, d), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (pool, PAGE, kv, d), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (pool, PAGE, kv, d), jnp.bfloat16)
    live_bytes = int(sum(min(n, window or n) for n in lens)) * kv * d * 2 * 2
    return (q, kp, vp, jnp.asarray(table, jnp.int32), jnp.asarray(lens, jnp.int32)), window, live_bytes


def chained(fn, calls: int, window: int):
    def run(q, kp, vp, table, lens):
        b, page, kv = q.shape[0], kp.shape[1], kp.shape[2]
        last = jnp.maximum(lens - 1, 0)
        slot = table[jnp.arange(b), last // page] * page + last % page
        slot = jnp.where(lens > 0, slot, (kp.shape[0] - 1) * page)
        flat = (-1, kv, kp.shape[3])
        for _ in range(calls):
            tok = q[:, :kv].astype(kp.dtype)
            kp = kp.reshape(flat).at[slot].set(tok).reshape(kp.shape)
            vp = vp.reshape(flat).at[slot].set(tok).reshape(vp.shape)
            q = fn(q, kp, vp, table, lens, window=window)
        return q

    return run


def device_ms(trace_dir: str, names):
    """Per program named ``jit_<name>``: its device time and the device
    time of its ``paged_decode_attention`` operations, in ms."""

    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    progs, kernel_ops = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    progs += [(e.name, e.start_ns, e.duration_ns) for e in line.events]
                elif line.name == "XLA Ops":
                    kernel_ops += [(e.start_ns, e.duration_ns) for e in line.events
                                   if "paged_decode_attention" in e.name]
    out = {}
    for n in names:
        runs = [(s, d) for name, s, d in progs if name.startswith(f"jit_{n}(")]
        out[n] = (
            [d * 1e-6 for _, d in runs],
            [1e-6 * sum(kd for ks, kd in kernel_ops if s <= ks < s + d) for s, d in runs],
        )
    return out


def load_kernel(spec: str):
    """``NAME=FILE``: the ``paged_decode_attention`` of another copy of the
    kernel module, e.g. a parent commit's, to time beside this one."""

    name, path = spec.split("=", 1)
    mod_spec = importlib.util.spec_from_file_location(f"paged_attention_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return name, mod.paged_decode_attention


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=2147483711)
    ap.add_argument("--calls", type=int, default=24)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--kernel", action="append", default=[], metavar="NAME=FILE")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    variants = {"kernel": pa.paged_decode_attention, **dict(map(load_kernel, args.kernel)),
                "gather": ref.paged_decode_attention_ref}
    results = []
    for shape in args.shapes.split(","):
        a, window, live_bytes = inputs(shape, args.seed)
        want = ref.paged_decode_attention_ref(
            a[0].astype(jnp.float32), a[1].astype(jnp.float32), a[2].astype(jnp.float32),
            a[3], a[4], window=window,
        )
        fns = {}
        for name, fn in variants.items():
            try:
                got = fn(*a, window=window).astype(jnp.float32)
            except Exception as e:  # a variant that does not compile at this shape
                print(json.dumps({"shape": shape, "variant": name, "error": str(e)[:300]}))
                continue
            err = float(jnp.max(jnp.abs(got - want)[a[4] > 0]))  # idle rows: see tests
            run = chained(fn, args.calls, window)
            run.__name__ = f"{shape}_{name}"
            fns[name] = (jax.jit(run), err)
            fns[name][0](*a).block_until_ready()  # compile outside the trace
        with tempfile.TemporaryDirectory() as tdir:
            with jax.profiler.trace(tdir):
                for _ in range(args.reps):
                    for f, _ in fns.values():
                        f(*a).block_until_ready()
            ms = device_ms(tdir, [f"{shape}_{n}" for n in fns])
        for name, (_, err) in fns.items():
            prog, kern = ms[f"{shape}_{name}"]
            if not prog:  # compiled to the same program as another variant
                print(json.dumps({"shape": shape, "variant": name, "error": "no program"}))
                continue
            per_call_us = 1e3 * statistics.median(prog) / args.calls
            kernel_us = 1e3 * statistics.median(kern) / args.calls
            row = {
                "shape": shape, "variant": name, "us_per_call": round(per_call_us, 2),
                "kernel_us": round(kernel_us, 2),
                "roofline_pct": round(100 * live_bytes / HBM_BYTES_PER_S * 1e6
                                      / (kernel_us or per_call_us), 2),
                "max_abs_err_vs_f32": err, "programs": len(prog),
            }
            results.append(row)
            print(json.dumps(row), flush=True)
    line = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": jax.device_count()}, "results": results}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(line, f, indent=1)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
