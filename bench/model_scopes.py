"""Device time under the model's own scopes, read from a ``--trace 1``
run's profiler trace.

``bench/spans.py`` reduces the round program's scopes (``spans.SCOPES``:
``local``, ``codec``, ``fuse``, ``server``). The model names its layers
inside ``local`` with scopes of its own (``repro.fl.runtime.SPANS`` lists
them): ``mla``, ``moe`` with ``route``, ``experts`` and ``shared`` inside
it, ``ffn`` and ``head``. For each, the union of the intervals of the ops
whose name stack holds it (``spans.in_scope``, bare or inside transforms),
clipped to the window, the mean over the chips. The TPU lowers the held
experts' grouped products (``jax.lax.ragged_dot``) to custom calls named
``ragged-dot-*`` whose name stack is their own name alone: they are
counted under ``moe`` and ``experts``, the only scopes that run them. The
trace file is read once for all of them and the result kept in the
reader's summary."""
from __future__ import annotations

import collections

from bench import spans, trace

SCOPES = ("mla", "moe", "route", "experts", "shared", "ffn", "head")
KEY = "model_scope_ns"
GROUPED = "%ragged-dot"       # op names of the TPU's grouped products


def scope_ns(ctx) -> dict | None:
    """{scope: device ns over the window, the mean over the chips}; None
    where the run's trace file cannot be found or is of another window."""
    s = ctx.summary
    if KEY not in s:
        s[KEY] = None
        path = spans._latest_trace()
        if path:
            data = spans.compact(path)
            lo, hi = next((a, a + d) for name, a, d in data["host"]
                          if name == trace.WINDOW)
            if hi - lo == s["window_ns"]:
                s[KEY] = _reduce(data, lo, hi)
    return s[KEY]


def _reduce(data: dict, lo: int, hi: int) -> dict:
    total = collections.Counter()
    for dev in data["devices"].values():
        scoped = collections.defaultdict(list)
        stacks = dev["stacks"]
        for (name, a, d), i in zip(dev["ops"], dev["op_scope"]):
            grouped = name.startswith(GROUPED)
            for scope in SCOPES:
                if spans.in_scope(stacks[i], scope) or (
                        grouped and scope in ("moe", "experts")):
                    scoped[scope].append(trace._clip(a, d, lo, hi))
        for scope, iv in scoped.items():
            total[scope] += sum(e - b for b, e in trace._union(iv))
    chips = max(len(data["devices"]), 1)
    return {k: v / chips for k, v in total.items()}


def ms_per_round(ctx, scope: str) -> float | None:
    ns = (scope_ns(ctx) or {}).get(scope)
    return ns / ctx.rounds / 1e6 if ns else None
