"""Program store: of ``programs.compile_s``, the seconds spent tracing and
lowering (``jitted.lower``); the rest is XLA's compile or the disk cache's
load.  The counters ``program_store.<namespace>.trace_seconds``, all
namespaces: a run of the benchmark is one process that builds nothing before
set-up and nothing after it (``no_retrace_in_window`` is part of
``correct``), so the process's total is the difference over set-up, as
``programs.compile_s`` is.  ``None`` where the program has no such counter."""


def read(obs):
    import mxnet_tpu as mx

    seconds = [getattr(ns, "trace_seconds", None)
               for ns in mx.program_store.NAMESPACES.values()]
    return sum(seconds) if seconds and None not in seconds else None
