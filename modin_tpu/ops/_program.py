"""Named device programs: the one place ``jax.jit`` is called.

Every jitted closure under ``ops/``, ``parallel/`` and the fused-plan
program of ``ops/lazy.py`` is built through :func:`named_jit`, which

- gives the program the name of its builder (``groupby_range_codes``,
  ``sort_lexsort``, ``plan_mod``), so a profiler trace reads
  ``jit_groupby_range_codes/fusion.1`` instead of ``jit_fn/fusion.1``;
- counts each launch into the open ``query_stats`` scopes where it happens
  (``launches`` / ``launches_by_program`` / ``first_launch_s``), so a device
  groupby that calls its kernels directly — never through
  ``JaxWrapper.deploy`` — is counted too; and
- where a launch made its program (a backend compile or persistent-cache
  load fired in the call), writes the temporary bytes the compiler gave it
  into the scopes' ``programs_made`` (``costs.program_memory``, read once a
  program and argument signature from the executable the call built).

With accounting off a launch costs one extra frame and two checks; with it on,
a launch that made nothing two reads of a thread-local counter besides.
"""

from __future__ import annotations

from typing import Any, Callable

from modin_tpu.observability import compile_ledger as _ledger
from modin_tpu.observability import meters as _meters
from modin_tpu.observability import spans as _spans

#: the trace reducer of ``benchmark/`` keeps programs named ``jit_bench_*``
#: out of the program's busy time: they are the harness's own
_RESERVED_PREFIX = "bench_"
_NAME_MAX = 48


class NamedProgram:
    """A jitted callable under a stable name.

    Calls go to the jitted function; every other attribute (``lower``,
    ``trace``, ``_cache_size``, ...) is the jitted function's own."""

    __slots__ = ("name", "_jitted", "__weakref__")

    def __init__(self, name: str, jitted: Any) -> None:
        self.name = name
        self._jitted = jitted

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        compiles = self._launching() if _meters.ACCOUNTING_ON else None
        # one line launches with accounting on and off: a Pallas kernel's
        # persistent-cache key holds the line it was traced from, so a traced
        # and an untraced process would not share its entry otherwise
        out = self._jitted(*args, **kwargs)
        if compiles is not None and _ledger.compiles_on_this_thread() != compiles:
            self._made(args, kwargs)
        return out

    def _launching(self) -> int:
        _meters.note_launch(self.name)
        return _ledger.compiles_on_this_thread()

    def _made(self, args: tuple, kwargs: dict) -> None:
        """The call compiled or loaded this program: its temporaries."""
        if not _spans.thread_requests():
            return
        from modin_tpu.observability.costs import program_memory

        memory = program_memory(self._jitted, args, kwargs)
        temp = memory.get("temp_bytes") if memory else None
        _meters.note_temp_bytes(self.name, int(temp) if isinstance(temp, float) else None)

    def __getattr__(self, attr: str) -> Any:
        return getattr(self._jitted, attr)

    def __repr__(self) -> str:
        return f"<NamedProgram jit_{self.name}>"


def named_jit(fn: Callable, name: str, **jit_kwargs: Any) -> NamedProgram:
    """``jax.jit(fn, **jit_kwargs)`` compiled and traced as ``jit_<name>``."""
    import jax

    name = name[:_NAME_MAX]
    if name.startswith(_RESERVED_PREFIX):
        raise ValueError(f"program name {name!r}: {_RESERVED_PREFIX}* is the benchmark harness's")
    # a wrapper of our own carries the name: the caller's function keeps its
    # own (it may be a library's, or shard_map's, with no settable name)
    def program(*args: Any, **kwargs: Any) -> Any:
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = name
    return NamedProgram(name, jax.jit(program, **jit_kwargs))


def traced_jit(fn: Callable, name: str) -> Any:
    """``jax.jit`` of a helper that other programs call while they are traced.

    Not a program and never a launch: the point is jax's own cache of the
    helper's trace by argument aval, so that ``jax.eval_shape`` of a node and
    the trace of a fused plan each see one ``jit`` call where the body's
    equations would be, and the columns of a frame share one lowered function.
    """
    import jax

    def helper(*args: Any, **kwargs: Any) -> Any:
        return fn(*args, **kwargs)

    helper.__name__ = helper.__qualname__ = name
    return jax.jit(helper)
