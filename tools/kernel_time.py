"""Time ``_paged_kernel`` ALONE on the chip, at the shapes of both decode
cells, this tree's beside another ``pallas_kernels.py`` (the parent's, or a
variant of this one) in ONE process — what PRs 28, 34 and 40 decided by.

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
    ap.add_argument("--cell", choices=sorted(CELLS) + ["both"],
                    default="both")
    ap.add_argument("--fill", default="cell,empty15,ragged,full",
                    help="comma-separated occupancies")
    ap.add_argument("--other", default=None,
                    help="another pallas_kernels.py to time beside this "
                         "tree's (other / this / this / other)")
    ap.add_argument("--reps", type=int, default=200)
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
                text = json.dumps(line)
                print(text, flush=True)
                with open(args.out, "a") as f:
                    f.write(text + "\n")


if __name__ == "__main__":
    main()
