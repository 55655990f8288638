"""Records the kernel calls of the traced window at the program's public
entry points (``repro.kernels.ops``), for the roofline readers.

Which entry points, and what one call's record holds, is the dataset
kind's (its ``KERNELS``: entry point -> ``take(entry, *args, **kw)``).
While the window is open the recorder swaps each entry point for a
wrapper, and puts the original back when it closes.  Each call made with
concrete arrays runs once, so it is one record.  A record may keep
arrays by reference; ``resolve`` runs each record's ``resolve()`` once
the window has closed, so the window makes no copy to the host.  A call
made while JAX traces a jitted function is not recorded: how often it
ran cannot be told from the call.  An entry point called from inside
another recorded one is not recorded again.
"""

from __future__ import annotations


def _is_tracer(x) -> bool:
    import jax

    return isinstance(x, jax.core.Tracer)


class KernelRecorder:
    """Context manager: while open, every concrete call of an entry point
    of ``entries`` appends its record to ``calls``."""

    def __init__(self, entries: dict):
        self.entries = entries
        self.calls: list = []
        self._saved: dict = {}
        self._depth = 0  # entry points that call others inside

    def _wrap(self, ops, entry: str, take):
        orig = getattr(ops, entry)

        def wrapper(*args, **kw):
            if self._depth == 0 and not any(map(_is_tracer, (*args, *kw.values()))):
                self.calls.append(take(entry, *args, **kw))
            self._depth += 1
            try:
                return orig(*args, **kw)
            finally:
                self._depth -= 1

        self._saved[entry] = orig
        setattr(ops, entry, wrapper)

    def __enter__(self):
        from repro.kernels import ops

        for entry, take in self.entries.items():
            self._wrap(ops, entry, take)
        return self

    def __exit__(self, *exc):
        from repro.kernels import ops

        for entry, orig in self._saved.items():
            setattr(ops, entry, orig)
        self._saved.clear()
        return False

    def resolve(self) -> list:
        """The records, each resolved."""
        for c in self.calls:
            c.resolve()
        return self.calls
