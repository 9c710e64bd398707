"""The layouts an engine holds its parameters in (ISSUE 38).

A stacked projection arrives row-major (``[L, in, out]``, the output
features minor), but the decode program's dots that feed ``rope`` and the
paged kernels read ``wq`` / ``wk`` (in some families ``wv``) with the
CONTRACTED dim minor. Compiled against row-major parameters, the program
re-lays those leaves out at the head of EVERY dispatch (0.84 GB read and
written a dispatch at Mistral-7B's 20 layers), although weights never change
between dispatches. So the engine asks the compiler instead: the decode
program is compiled with every parameter leaf's layout left to the compiler
(``Layout.AUTO``), the weights are committed ONCE to the formats it chose, and
the chunk program is compiled against those formats. A leaf whose chosen
layout is the device's default is left where it lies (the same buffer).

Where the two programs would choose differently the decode program wins: it
runs ``decode_horizon`` token-steps a dispatch and in every step, and a chunk
program's per-layer transposing fetch costs the same bytes as a plain one.

``scripts/programs_hlo.py`` and ``tests/test_aot_topology.py`` compile their
programs through :func:`held_layout_programs` too (abstract parameters on a
described chip), so what they read is what the engine runs.
"""

from __future__ import annotations

import os
import re
import time

import jax
from jax.experimental.layout import Format, Layout

_tree = jax.tree_util


def held_layout_programs(step, chunk, params, step_rest):
    """``(decode, chunk, formats)`` for the engine's ``step(params, token,
    pos, pool, ...)`` and ``chunk(params, tokens, start, n, pool, row)``:
    the decode program COMPILED for ``(params, *step_rest)`` with the layout
    of every leaf of ``params`` left to the compiler, the ``Format`` it chose
    a leaf (a tree like ``params``), and the chunk program as a ``jax.jit``
    that takes its parameters in those formats. Both donate the pool.
    ``params`` and ``step_rest`` may be arrays or ``jax.ShapeDtypeStruct``s
    (only shape, dtype and sharding are read)."""
    spec = _tree.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        params)
    ask = _tree.tree_map(lambda a: Format(Layout.AUTO, a.sharding), spec)
    decode = jax.jit(
        step, in_shardings=(ask,) + (None,) * len(step_rest),
        donate_argnums=(3,)).lower(spec, *step_rest).compile()
    formats = decode.input_formats[0][0]
    chunk = jax.jit(chunk, in_shardings=(formats,) + (None,) * 5,
                    donate_argnums=(4,))
    return decode, chunk, formats


def default_layout(fmt: Format, leaf) -> Layout:
    """The layout ``leaf``'s device gives an array of its shape and dtype
    when nobody asks for another."""
    dev = next(iter(fmt.sharding.device_set))
    return Layout.from_pjrt_layout(dev.client.get_default_layout(
        leaf.dtype, fmt.sharding.shard_shape(leaf.shape), dev))


def relaid(params, formats) -> list[dict]:
    """The leaves of ``params`` whose chosen format is NOT their device's
    default, each ``{"leaf", "shape", "from", "to", "bytes"}`` (layouts as
    XLA writes them: minor-to-major)."""
    out = []
    flat = _tree.tree_leaves_with_path(params)
    for (path, leaf), fmt in zip(flat, _tree.tree_leaves(formats)):
        default = default_layout(fmt, leaf)
        if fmt.layout is not None and fmt.layout != default:
            out.append({
                "leaf": _tree.keystr(path), "shape": list(leaf.shape),
                "from": _minor_to_major(default),
                "to": _minor_to_major(fmt.layout),
                "bytes": int(leaf.size) * leaf.dtype.itemsize})
    return out


def _minor_to_major(layout: Layout) -> str:
    return "{" + ",".join(str(d) for d in layout.major_to_minor[::-1]) + "}"


def _hold(leaves):
    return leaves


# ``commit``'s one program, under a name no other process has compiled under.
# On jax 0.9.0 with this libtpu an executable LOADED from the persistent
# compilation cache reports the default layout for its outputs whatever it
# writes (parameters' layouts survive): a re-layout that hit the cache came
# back labelled row-major with its bytes the other way round (my chip runs,
# PR 38, call 2: values differ). So the one program here whose OUTPUT layout
# matters is never loaded: its name, which is part of the cache's key, is this
# process's own.
_hold.__name__ = _hold.__qualname__ = (
    f"hold_as_asked_{os.getpid()}_{time.time_ns():x}")


def commit(params, formats):
    """``params`` with every leaf in its chosen format: a leaf already there
    is returned as it is; the others are copied, all by one program, once
    (the originals are the caller's, and are left alone)."""
    flat, tree = _tree.tree_flatten(params)
    fmts = _tree.tree_leaves(formats)
    # (a format without a layout, as the CPU's are, asks for nothing)
    held = lambda a, fmt: fmt.layout is None or a.format == fmt  # noqa: E731
    move = [i for i, (a, fmt) in enumerate(zip(flat, fmts))
            if not held(a, fmt)]
    if move:
        moved = jax.jit(_hold, out_shardings=[fmts[i] for i in move])(
            [flat[i] for i in move])
        for i, a in zip(move, moved):
            if not held(a, fmts[i]):
                raise RuntimeError(
                    f"asked for {fmts[i]}, the runtime handed back "
                    f"{a.format}: refusing to serve from weights whose "
                    "bytes may not be laid out as they are labelled")
            flat[i] = a
    return _tree.tree_unflatten(tree, flat)


_INSTR = re.compile(r"\s*(?:ROOT )?(%[\w.\-]+) = (.+?) ([\w\-]+)\((.*)")


def _layout_of(result: str) -> str:
    """``{1,2,0:T(8,128)(2,1)}`` of an instruction's (first) result, without
    its memory space: a prefetch into on-chip memory re-lays nothing out."""
    m = re.search(r"\{([^}]*)\}", result)
    return re.sub(r"S\(\d+\)", "", m.group(1)) if m else ""


def entry_copies(hlo_text: str, params) -> list[str]:
    """The instructions of an optimised program's ENTRY computation that
    re-lay out a PARAMETER of a leaf of ``params``' shape (as it came, or
    after a prefetch that left its layout alone): a ``copy`` / ``copy-start``
    whose result's layout is not its operand's, a ``transpose``, or a fusion
    named for either. It is what a program compiled against layouts it does
    not read in does at the head of every dispatch; empty for programs through
    :func:`held_layout_programs`."""
    shapes = {"[" + ",".join(str(d) for d in leaf.shape) + "]"
              for leaf in _tree.tree_leaves(params) if leaf.ndim >= 2}
    entry = hlo_text[hlo_text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    held = {}                   # a weight parameter (or a bitcast) -> layout
    found = []
    for line in entry.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, result, opcode, rest = m.groups()
        operands = re.findall(r"%[\w.\-]+", rest.split("), ")[0])
        if opcode == "parameter":
            if re.sub(r"^\w+|\{.*", "", result) in shapes:
                held[name] = _layout_of(result)
        elif any(o in held for o in operands):
            kind = name if opcode == "fusion" else opcode
            layout = _layout_of(result)
            if opcode == "bitcast" or (re.search(r"copy", kind) and any(
                    held.get(o) == layout for o in operands)):
                # the same bytes under another name, or moved as they lie
                # (a prefetch into on-chip memory and its copy-done)
                held[name] = layout
            elif re.search(r"copy|transpose", kind):
                found.append(line.strip()[:200])
    return found


__all__ = ["commit", "default_layout", "entry_copies",
           "held_layout_programs", "relaid"]
