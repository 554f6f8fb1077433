"""A dispatch's host arguments as ONE buffer.

A step program's call carries every numpy argument to the device as a
transfer of its own, inside the gated interval, and it is the count of
transfers that costs, not their bytes (the largest, the lanes' tables, is
32-64 KB; the rest are 4 B to 2 KB).  :class:`PackedProgram` keeps the
calling convention of the program it wraps — ``step(params, pool_k, pool_v,
*arguments)`` — and inside lays every host array among ``arguments`` end to
end into one ``uint32`` buffer (``int32`` / ``uint32`` / ``float32`` by
``.view``, ``bool`` widened), which the jitted program slices apart again
by offsets that are static: bits in are bits out, so the program's
mathematics are those of the function it was given.

The layout is what the call observes in its arguments — a ``(shape, dtype)``
an argument that rides in the buffer, ``None`` for what is passed as it is
(a device array, a pytree of them, a donated argument) — computed once a
signature and kept; it is the jitted program's one static argument, so a
program compiles once a signature, as the bare ``jax.jit`` did.  The buffer
itself is made anew every call: the backend may read it after the call
returns (on the CPU it may alias it), so it is never written again.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Tuple

import jax
import numpy as np
from jax import lax

__all__ = ["Layout", "PackedProgram", "layout_of", "pack", "unpack", "words"]

# (shape, dtype) of an argument that rides in the buffer; None: passed as is
Layout = Tuple[Optional[Tuple[Tuple[int, ...], np.dtype]], ...]

WORD = np.dtype(np.uint32)
# what a dispatch carries: every one is a word an element, or widened to one
_PACKABLE = frozenset(np.dtype(t) for t in (np.int32, np.uint32, np.float32,
                                            np.bool_))


def layout_of(args, passed_on=(), host=np.ndarray) -> Layout:
    """The layout of ``args``: an argument that is a ``host`` array of a
    packable dtype rides in the buffer, but one whose index is in
    ``passed_on`` (a donated argument: the program takes it over whole)."""
    return tuple(
        (tuple(x.shape), x.dtype)
        if i not in passed_on and isinstance(x, host)
        and x.dtype in _PACKABLE else None
        for i, x in enumerate(args))


def words(layout: Layout) -> int:
    """The buffer's length: an element a word."""
    return sum(math.prod(entry[0]) for entry in layout if entry is not None)


def pack(layout: Layout, args) -> np.ndarray:
    """The arguments that ride, end to end in a NEW ``uint32`` buffer: a
    bool as 0 / 1, the others by their bits."""
    return np.concatenate([
        flat.astype(WORD) if flat.dtype == np.bool_ else flat.view(WORD)
        for flat in (x.reshape(-1) for entry, x in zip(layout, args)
                     if entry is not None)] or [np.empty((0,), WORD)])


def unpack(layout: Layout, packed, passed) -> list:
    """The arguments again, inside the jitted program: the buffer's slices
    by static offsets (floats bitcast back, bools compared with 0) with
    ``passed`` (an iterator over what did not ride) in their places."""
    args, at = [], 0
    for entry in layout:
        if entry is None:
            args.append(next(passed))
            continue
        shape, dtype = entry
        n = math.prod(shape)
        piece = lax.slice(packed, (at,), (at + n,)).reshape(shape)
        args.append(piece != 0 if dtype == np.bool_
                    else lax.bitcast_convert_type(piece, dtype))
        at += n
    return args


class PackedProgram:
    """``fn(params, pool_k, pool_v, *arguments)`` jitted under ``name`` so
    that a call's host arguments cross to the device as one buffer.

    ``donate_argnums`` are ``fn``'s; a donated argument past the pool (a
    model's states by slot) never rides.  ``carried(count, nbytes)``, if
    given, hears of every call's host arrays that went to the device: the
    buffer, and any host array the layout had to pass on."""

    LEAD = 3  # params, pool_k, pool_v: device arrays, passed as they are

    def __init__(self, name: str, fn: Callable, donate_argnums=(),
                 carried: Optional[Callable[[int, int], None]] = None):
        self.fn, self.carried = fn, carried
        self.__name__ = self.__qualname__ = name
        self._donated = frozenset(
            i - self.LEAD for i in donate_argnums if i >= self.LEAD)
        # layout -> (the arguments it passes on, in `inner`'s order; the
        # buffer's bytes)
        self._kept: Dict[Layout, Tuple[Tuple[int, ...], int]] = {}

        def step(layout, w, pool_k, pool_v, packed, *passed):
            # `passed` back in the arguments' order
            by_index = dict(zip(self._order(layout), passed))
            return fn(w, pool_k, pool_v, *unpack(
                layout, packed, map(by_index.get, sorted(by_index))))

        step.__name__ = step.__qualname__ = name  # the XLA module's: jit_<name>
        passed_on = range(self.LEAD + 2, self.LEAD + 2 + len(self._donated))
        self.inner = jax.jit(
            step, static_argnums=0,
            donate_argnums=tuple(i + 1 for i in donate_argnums
                                 if i < self.LEAD) + tuple(passed_on))

    def _order(self, layout: Layout) -> Tuple[int, ...]:
        """The arguments ``layout`` passes on, the donated ones first:
        ``inner``'s ``donate_argnums`` are fixed, a layout is not."""
        on = [i for i, entry in enumerate(layout) if entry is None]
        return tuple(sorted(on, key=lambda i: i not in self._donated))

    def __call__(self, w, pool_k, pool_v, *rest):
        layout = layout_of(rest, self._donated)
        kept = self._kept.get(layout)
        if kept is None:
            kept = self._kept[layout] = (self._order(layout),
                                         words(layout) * WORD.itemsize)
        order, nbytes = kept
        passed = [rest[i] for i in order]
        if self.carried is not None:
            on_host = [x.nbytes for x in passed if isinstance(x, np.ndarray)]
            self.carried(bool(nbytes) + len(on_host), nbytes + sum(on_host))
        return self.inner(layout, w, pool_k, pool_v, pack(layout, rest),
                          *passed)

    def lower(self, w, pool_k, pool_v, *rest):
        """The program of a call with these arguments — arrays or their
        ``jax.ShapeDtypeStruct``s, which no longer say where an argument
        lived: the kept layout that fits them (the call that ran), or the
        one in which every packable argument rides."""
        layout = self._layout_for(rest)
        packed = jax.ShapeDtypeStruct((words(layout),), WORD)
        return self.inner.lower(layout, w, pool_k, pool_v, packed,
                                *(rest[i] for i in self._order(layout)))

    def _layout_for(self, rest) -> Layout:
        every = layout_of(rest, self._donated,
                          (np.ndarray, jax.Array, jax.ShapeDtypeStruct))
        for layout in self._kept:
            if len(layout) == len(every) and all(
                    entry is None or entry == said
                    for entry, said in zip(layout, every)):
                return layout
        return every

    def _cache_size(self) -> int:
        return self.inner._cache_size()
