"""Time ``_paged_kernel`` — or, with ``--kernel band``, the prefill's
``_band_kernel`` — ALONE on the chip, at the shapes of the decode cells,
this tree's beside another ``pallas_kernels.py`` (the parent's, or a variant
of this one) in ONE process — what PRs 28, 34, 40 and 43 decided by.

A tick's launches are chained in one jitted function (launch ``i + 1`` takes
launch ``i``'s output as its queries, so they run in order as the step's
do): 24 over one table at OPT's widths (16 slots, 32 x 64 heads in rows 128
wide, 128 columns, the compiler's default precision), 1 + 4 over a full
table of 512 columns and a ring of 257 at Trinity's (48 / 8 heads x 128,
float32 products). The pools are ARGUMENTS of the function (closed over,
they are constants and a compile takes minutes). What is timed is the
device's time of the whole chain — the walk's schedule in front of the
kernels included, once a table as in the step — over ``--reps`` calls
enqueued back to back, per launch.

Occupancies (``--fill``): ``cell`` what the benchmark's traffic leaves live
(one slot of 16 at 304 tokens on OPT; two at 2,640 and 1,584 on Trinity),
``empty15`` one live slot LAST behind fifteen empty ones, ``ragged`` all
sixteen live at seeded log-normal lengths, ``full`` every column live.

``--kernel band``: a Trinity prefill's five launches of ``band_attention``
(48 / 8 heads x 128, float32 products; four over the window of 4096, one
over every key) chained the same way, at rungs 2048 / 4096 / 8192 with the
prompt's real tokens the whole rung, 0.73 of it and one past its half
(``--rungs``, ``--real``). A ``pallas_kernels.py`` whose ``band_attention``
takes no ``length`` (PR 40's) computes the whole rung. ``--band-blocks``
times this tree's kernel at other block sizes (rows of a query block =
columns of a kv block) beside the one it keeps: how the constant was chosen
(PERF.md section 6, PR 43). The two sides' largest absolute gap over the
real rows rides each line.

Run on the chip, from the repo's root:

    chiprun -- python tools/kernel_time.py --other .scratch/parent/mxnet_tpu/ops/pallas_kernels.py

Each result is one JSON line, printed and appended to
``chiprun_out/kernel_time.jsonl`` as it comes (a call killed at its limit
returns nothing else). ``--other`` runs other / this / this / other and
reports whether the two agree bit for bit. Off the chip (``--interpret``,
tiny shapes) it only proves the script's control flow: never a time.
"""
import argparse
import json
import os
import sys
import time
import types
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

SLOTS, PAGE = 16, 16
# launches of a tick by table: (kind, columns, pool pages, launches)
CELLS = {
    "opt": dict(heads=32, kv_heads=32, head_dim=64, row=128, precise=False,
                tables=[("full", 128, 385, 24)]),
    "trinity": dict(heads=48, kv_heads=8, head_dim=128, row=128,
                    precise=True, window=4096,
                    tables=[("full", 512, 4097, 1),
                            ("ring", 257, SLOTS * 257 + 1, 4)]),
}
CELL_LENGTHS = {"opt": {3: 304}, "trinity": {3: 2640, 9: 1584}}


def load_kernels(path):
    """``pallas_kernels.py`` at ``path`` as a module of its own (the op
    registry refuses a second registration: the registering call at its end
    is cut)."""
    import mxnet_tpu.ops.pallas_kernels as mine

    if path is None:
        return mine
    source = Path(path).read_text().replace(
        "\n_register_flash_attention_op()\n", "\n")
    mod = types.ModuleType("other_pallas_kernels")
    mod.__package__ = mine.__package__
    mod.__file__ = str(path)
    exec(compile(source, str(path), "exec"), mod.__dict__)
    return mod


def lengths(cell, fill, columns, rng):
    """Tokens live a slot, (SLOTS,) int32, at most what a table of
    ``columns`` holds (the cell's FIRST table: a ring behind it wraps)."""
    cap = columns * PAGE
    out = np.zeros(SLOTS, np.int64)
    if fill == "cell":
        for slot, n in CELL_LENGTHS[cell].items():
            out[slot] = n
    elif fill == "empty15":
        out[SLOTS - 1] = max(CELL_LENGTHS[cell].values())
    elif fill == "ragged":
        out[:] = np.exp(rng.normal(np.log(cap / 8.0), 0.8, SLOTS))
        out = np.clip(out, PAGE, cap)
    elif fill == "full":
        out[:] = cap
    else:
        raise ValueError(fill)
    return np.minimum(out, cap).astype(np.int32)


def build(pk, cell, interpret):
    """The chain of a tick's launches over ``pk``'s kernel: ``fn(q, pools,
    tables, lens)`` -> the last launch's output."""
    import jax

    shape = CELLS[cell]

    def fn(q, pools, tables, lens):
        for (kind, _cols, _pages, launches), (kp, vp), table in zip(
                shape["tables"], pools, tables):
            for _ in range(launches):
                if kind == "ring":
                    q = pk.ragged_window_attention(
                        q, kp, vp, table, lens, shape["window"],
                        interpret=interpret, precise=shape["precise"])
                else:
                    q = pk.ragged_paged_attention(
                        q, kp, vp, table, lens, interpret=interpret,
                        precise=shape["precise"])
        return q

    return jax.jit(fn)


def operands(cell, fill, seed, tiny):
    import jax.numpy as jnp

    shape = CELLS[cell]
    rng = np.random.RandomState(seed)
    row = 16 if tiny else shape["row"]
    dim = 16 if tiny else shape["head_dim"]
    q = jnp.asarray(rng.randn(SLOTS, shape["heads"], dim).astype(np.float32))
    pools, tables, live = [], [], []
    lens = None
    for kind, cols, pages, launches in shape["tables"]:
        if tiny:
            cols, pages = 6, SLOTS * 6 + 1
        pools.append(tuple(
            jnp.asarray(rng.randn(pages, PAGE, shape["kv_heads"], row)
                        .astype(np.float32)) for _ in range(2)))
        tables.append(jnp.asarray(
            1 + rng.permutation(SLOTS * cols).reshape(SLOTS, cols)
            % (pages - 1), jnp.int32))
        if lens is None:        # the lengths are the FIRST table's
            lens = lengths(cell, fill, cols, rng)
        live.append(launches * np.minimum(-(-lens // PAGE), cols))
    return (q, pools, tables, jnp.asarray(lens)), int(np.sum(live))


BAND = dict(heads=48, kv_heads=8, head_dim=128,
            launches=(4096, 4096, 4096, 4096, 0))   # a launch's window
BAND_TINY = dict(heads=12, kv_heads=2, head_dim=16,
                 launches=(32, 32, 0))


def build_band(pk, shape, interpret, block=None):
    """The chain of a prefill's launches over ``pk``'s kernel: ``fn(q, k, v,
    length)`` -> the last launch's output. ``block``: another block size
    than the kernel's own."""
    import inspect

    import jax

    takes_length = "length" in inspect.signature(
        pk.band_attention).parameters

    def fn(q, k, v, length):
        more = {"block": block} if block else {}
        if takes_length:
            more["length"] = length
        for window in shape["launches"]:
            q = pk.band_attention(q, k, v, window=window, precise=True,
                                  interpret=interpret, **more)
        return q

    return jax.jit(fn)


def band_main(args, device, sides):
    import jax.numpy as jnp

    shape = BAND_TINY if args.interpret else BAND
    rungs = [64] if args.interpret else [int(r) for r in
                                         args.rungs.split(",")]
    chains = {name: build_band(pk, shape, args.interpret)
              for name, pk in dict(sides).items()}
    variants = [(name, chains[name]) for name, _pk in sides]
    for spec in filter(None, args.band_blocks.split(",")):
        variants.append(("this@" + spec, build_band(
            dict(sides)["this"], shape, args.interpret, int(spec))))
    for rung in rungs:
        rng = np.random.RandomState(args.seed)
        q = jnp.asarray(rng.randn(rung, shape["heads"], shape["head_dim"])
                        .astype(np.float32))
        k, v = (jnp.asarray(rng.randn(rung, shape["kv_heads"],
                                      shape["head_dim"]).astype(np.float32))
                for _ in range(2))
        for real in args.real.split(","):
            n = rung // 2 + 1 if real == "half+1" else \
                int(round(float(real) * rung))
            ops = (q, k, v, jnp.asarray(n, jnp.int32))
            outs = {}
            for name, fn in variants:
                secs, out = time_chain(fn, ops,
                                       1 if args.interpret else args.reps)
                outs[name] = np.asarray(out)[:n]
                line = {"kernel": "band", "rung": rung, "real": n,
                        "side": name, "device": device.device_kind,
                        "launches": len(shape["launches"])}
                if not args.interpret:
                    line["ms_per_chain"] = secs * 1e3
                if name != "other" and "other" in outs:
                    line["max_abs_gap"] = largest_gap(outs[name],
                                                      outs["other"])
                emit(line, args.out)


def largest_gap(a, b):
    """Largest absolute difference of two host arrays."""
    return float(np.abs(a - b).max())


def emit(line, path):
    text = json.dumps(line)
    print(text, flush=True)
    with open(path, "a") as f:
        f.write(text + "\n")


def time_chain(fn, args, reps):
    """Seconds a call: ``reps`` calls enqueued back to back, one wait."""
    fn(*args).block_until_ready()
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / reps, out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--kernel", choices=["paged", "band"], default="paged")
    ap.add_argument("--cell", choices=sorted(CELLS) + ["both"],
                    default="both")
    ap.add_argument("--fill", default="cell,empty15,ragged,full",
                    help="comma-separated occupancies")
    ap.add_argument("--other", default=None,
                    help="another pallas_kernels.py to time beside this "
                         "tree's (other / this / this / other)")
    ap.add_argument("--rungs", default="2048,4096,8192",
                    help="band: the prefill rungs")
    ap.add_argument("--real", default="1.0,0.73,half+1",
                    help="band: the prompt's real tokens, shares of the "
                         "rung (half+1: one past its half)")
    ap.add_argument("--band-blocks", default="",
                    help="band: other block sizes of this tree's kernel to "
                         "time too, e.g. 128,512")
    ap.add_argument("--reps", type=int, default=None,
                    help="calls enqueued back to back (default 200; band: "
                         "8)")
    ap.add_argument("--seed", type=int, default=40)
    ap.add_argument("--interpret", action="store_true",
                    help="tiny shapes in interpret mode (no chip: proves "
                         "the control flow, prints no time)")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" /
                                         "kernel_time.jsonl"))
    args = ap.parse_args(argv)

    import jax

    device = jax.devices()[0]
    if not args.interpret and device.platform != "tpu":
        sys.exit("kernel_time: no TPU here (%s); --interpret only proves "
                 "the control flow" % device.platform)
    sides = [("this", load_kernels(None))]
    if args.other:
        other = ("other", load_kernels(args.other))
        sides = [other, sides[0], sides[0], other]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    if args.reps is None:
        args.reps = 8 if args.kernel == "band" else 200
    if args.kernel == "band":
        return band_main(args, device, sides)
    cells = sorted(CELLS) if args.cell == "both" else [args.cell]
    for cell in cells:
        chains = {name: build(pk, cell, args.interpret)
                  for name, pk in dict(sides).items()}
        launches = sum(t[3] for t in CELLS[cell]["tables"])
        for fill in args.fill.split(","):
            ops, live = operands(cell, fill, args.seed, args.interpret)
            outs = {}
            for name, _pk in sides:
                secs, out = time_chain(chains[name], ops,
                                       1 if args.interpret else args.reps)
                outs[name] = np.asarray(out)
                line = {"cell": cell, "fill": fill, "side": name,
                        "device": device.device_kind,
                        "launches": launches, "live_columns": live}
                if not args.interpret:
                    line.update(us_per_chain=secs * 1e6,
                                us_per_launch=secs * 1e6 / launches)
                if len(outs) == 2:
                    line["bitwise_equal"] = bool(np.array_equal(
                        outs["this"], outs["other"], equal_nan=True))
                emit(line, args.out)


if __name__ == "__main__":
    main()
