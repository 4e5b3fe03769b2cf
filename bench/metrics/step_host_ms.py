"""Mean host time of one engine step outside its forwards (sweep,
admission, input arrays, sampling, bookkeeping):
`(step_s - prefill_s - decode_s) / steps` of the engine's `stats()` over
the window, less the steps the profiler's trace recorded (the profiler
slows the host while it records). None where the program keeps no step
time."""

from harness.scopes import untraced_step_host_ms


def read(ctx):
    return untraced_step_host_ms(ctx)
