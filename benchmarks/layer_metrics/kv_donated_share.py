"""Share of the calls of pool-writing programs (prefill, extend, decode,
speculative verify, block copy) that consumed the KV pools they were
handed and wrote them in place: ``serving.kv.donated_calls`` over it plus
``serving.kv.copied_calls`` over the window, times 100. The program
looks at the first pool it handed in after each call (``is_deleted()``).
It should read 100; a backend or a sharding that refuses the donation
reads 0, and every such call then copies every pool. None where the
program has no such counters."""


def read(ctx):
    counters = ctx["counters"]
    if "serving.kv.donated_calls" not in counters:
        return None
    donated = counters["serving.kv.donated_calls"]
    calls = donated + counters.get("serving.kv.copied_calls", 0)
    return 100.0 * donated / calls if calls else None
