"""Device milliseconds a local step the flat carry costs around the update
kernel: `step/unflatten` (the leaf views) plus `step/update` (gradient
flatten, pack, pads, unpack) less the named kernel's own `custom-call`, which
`update_kernel_ms.step` reads."""

from benchmark import scope_reduce

_unflatten = scope_reduce.has("step/unflatten")
_update = scope_reduce.has("step/update")


def compute(reduction, phases, cell):
    return scope_reduce.ms(
        reduction,
        lambda r: (_unflatten(r) or _update(r)) and not scope_reduce.kernel_call(r),
        cell["steps_per_round"])
