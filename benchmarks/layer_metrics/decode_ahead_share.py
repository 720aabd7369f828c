"""Share of the decode dispatches made while the step before was still
unread on the device: ``serving.decode.ahead`` over it plus
``serving.decode.in_order`` over the window, times 100. The plain decode
loop runs one step ahead (``serving/scheduler.py _decode``): it
dispatches in order only after idle, after something other than an
admission or a finish by count changed who runs (a preemption, a swept
running request), or when the dispatch built its program. It should
read near 100 where slots stay full. None where the program has no such
counters."""


def read(ctx):
    counters = ctx["counters"]
    if "serving.decode.ahead" not in counters:
        return None
    ahead = counters["serving.decode.ahead"]
    calls = ahead + counters.get("serving.decode.in_order", 0)
    return 100.0 * ahead / calls if calls else None
