"""Device events of each layer, by the names a v5e trace gives them.

Pallas kernels here pass no ``name=``, and their bodies share names
(``_kernel`` is the single-ball, predict and gram body alike), so an event is
matched by the jitted entry's HLO module together with the Mosaic custom
call inside it (the one ``tpu_custom_call`` of the module: the engine's
instruction is ``streamsvm_fit_many.1``). PERF.md lists the names as read by
hand from a chip trace.
"""
from __future__ import annotations

#: Modules that run a bank fit: the single-device wrapper, and the
#: shard_map program of ``fit_bank(mesh=)``.
TRAIN_MODULES = ("jit_streamsvm_fit_many", "jit__sharded_fits")
#: The serving entry ``kernels.ops.predict_bank``.
PREDICT_MODULES = ("jit_predict_bank",)
#: The kind xplane gives an op that launches a Mosaic (Pallas) kernel.
KERNEL_KIND = "tpu_custom_call"


def is_kernel(e) -> bool:
    return e.kind == KERNEL_KIND


def is_train(e) -> bool:
    return e.module in TRAIN_MODULES


def is_engine(e) -> bool:
    """The training engine, ``_kernel_many``, inside a fit."""
    return is_train(e) and is_kernel(e)


def is_predict(e) -> bool:
    """The serving kernel inside ``predict_bank``."""
    return e.module in PREDICT_MODULES and is_kernel(e)


def passes(trace) -> list:
    return trace.spans_named("bench.pass")


def steps(trace) -> list:
    return trace.spans_named("serve.step")


def per_device(trace, pred) -> list[float]:
    """Seconds of matching ops in the window, one entry per device."""
    return [sum(e.dur for e in trace.ops(p) if pred(e)) * 1e-9
            for p in trace.devices]
