"""Records the shapes of the support-count kernel's calls at their public
entry points (``repro.kernels.ops``), for the roofline reader.

While the window is open the recorder swaps each entry point for a
wrapper, and puts the original back when it closes.  Each call made with
concrete arrays runs once, so it is one ``Call``; its candidate masks are
kept by reference, and their non-empty rows are counted once the window
has closed (``resolve``), so the window makes no copy to the host.  A
call made while JAX traces a jitted function is not recorded: how often
it ran cannot be told from the call.  Shapes are the problem's:
candidate rows that are all zero (padding) are not counted, and the
lane padding that the entry points add themselves never reaches the
wrapper.
"""

from __future__ import annotations

from dataclasses import dataclass

ENTRIES = {"support_count": False, "support_count_prune": False,
           "support_count_sites": True, "support_count_prune_sites": True}


@dataclass
class Call:
    entry: str  # the ops function called
    sites: int
    n_tx: int  # rows a site
    words: int  # 32-bit words a row
    masks: object  # the candidate masks, until resolved
    n_cand_total: int | None = None  # non-empty candidates over all sites


def _is_tracer(x) -> bool:
    import jax

    return isinstance(x, jax.core.Tracer)


class KernelRecorder:
    """Context manager: while open, every concrete call of an entry point
    appends a ``Call`` to ``calls``."""

    def __init__(self):
        self.calls: list[Call] = []
        self._saved: dict = {}
        self._depth = 0  # the *_sites forms call the single forms inside

    def _wrap(self, ops, entry: str, sites: bool):
        orig = getattr(ops, entry)

        def wrapper(tx, masks, *args, **kw):
            if self._depth == 0 and not (_is_tracer(tx) or _is_tracer(masks)):
                s, n, w = tx.shape if sites else (1, *tx.shape)
                self.calls.append(Call(entry=entry, sites=s, n_tx=n, words=w, masks=masks))
            self._depth += 1
            try:
                return orig(tx, masks, *args, **kw)
            finally:
                self._depth -= 1

        self._saved[entry] = orig
        setattr(ops, entry, wrapper)

    def __enter__(self):
        from repro.kernels import ops

        for entry, sites in ENTRIES.items():
            self._wrap(ops, entry, sites)
        return self

    def __exit__(self, *exc):
        from repro.kernels import ops

        for entry, orig in self._saved.items():
            setattr(ops, entry, orig)
        self._saved.clear()
        return False

    def resolve(self) -> list[Call]:
        """The calls, each with its non-empty candidates counted from its
        masks (all-zero rows are padding); drops the masks."""
        import numpy as np

        for c in self.calls:
            if c.masks is not None:
                m = np.asarray(c.masks).reshape(-1, c.words)
                c.n_cand_total = int((m != 0).any(axis=1).sum())
                c.masks = None
        return self.calls
