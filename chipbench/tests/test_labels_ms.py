"""labels_ms on reduced traces made by hand: the named labels jit's device
time per delivery, nothing where the trace holds no such op."""

from types import SimpleNamespace

import pytest

from chipbench import harness
from chipbench.metrics import labels_ms


def _run(op_s, deliveries=4):
    done = [harness.Delivery(i, 0.0, 1.0, SimpleNamespace(), 0) for i in range(deliveries)]
    trace = None if op_s is None else {"window_s": 1.0, "busy_s": 0.5, "op_s": op_s}
    return harness.Run(cell=None, seconds=1.0, setup_s=0.0, window_s=1.0,
                       deliveries=done, due=deliveries, missing_latency_s=[],
                       trace=trace)


def test_device_time_of_the_labels_jit_per_delivery():
    op_s = {"jit_labels/%iota_reduce_fusion": 0.010, "jit_labels/%copy.1": 0.002,
            "jit_bound/%meshnet_apply.3": 1.5, "jit__argmax/%iota_reduce_fusion": 0.4,
            "%labels_elsewhere": 0.3}
    assert labels_ms.read(_run(op_s)) == pytest.approx(3.0)


@pytest.mark.parametrize("op_s,deliveries", [
    (None, 4),  # an untraced run
    ({"jit_bound/%meshnet_apply.3": 1.5}, 4),
    # the argmax of a program without the named jit
    ({"jit__argmax/%iota_reduce_fusion": 0.4}, 4),
    ({"jit_labels/%iota_reduce_fusion": 0.01}, 0),
])
def test_nothing_to_read(op_s, deliveries):
    assert labels_ms.read(_run(op_s, deliveries)) is None
