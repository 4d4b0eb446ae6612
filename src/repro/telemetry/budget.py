"""Memory-budget simulator — the browser's failure modes, parameterised.

The paper's fail taxonomy (Table V): "Failed to compile fragment shader",
"Failed to link shaders", "Unable to create WebGL Texture" — all memory /
resource-limit manifestations. On TPU the corresponding wall is HBM bytes
per device (and VMEM per kernel block). This module prices each inference
strategy's peak working set against a configurable budget, so the
engine's guard can choose full-volume or failsafe sub-volume mode per
request, as the paper's patching and cropping interventions do.

The budget model is deliberately analytic (bytes, not wall-clock): it is
the part of the paper we *simulate* because the actual gate (a fleet of
heterogeneous browsers) does not exist in this container. DESIGN.md §1.
"""

from __future__ import annotations

import dataclasses
import math
from typing import TYPE_CHECKING

if TYPE_CHECKING:  # annotation-only: a runtime import would cycle back
    # through repro.core (core.pipeline imports this module).
    from repro.core.meshnet import MeshNetConfig

V5E_HBM_BYTES = 16 * 1024**3  # 16 GB HBM per v5e chip


class BudgetExceeded(Exception):
    def __init__(self, fail_type: str, need: int, have: int):
        super().__init__(f"{fail_type}: need {need} bytes, budget {have}")
        self.fail_type = fail_type
        self.need = need
        self.have = have


@dataclasses.dataclass(frozen=True)
class MemoryBudget:
    """A per-run memory budget in bytes (the simulated device)."""

    bytes_limit: int
    name: str = "custom"

    @staticmethod
    def unlimited() -> "MemoryBudget":
        return MemoryBudget(bytes_limit=1 << 62, name="unlimited")

    @staticmethod
    def v5e() -> "MemoryBudget":
        return MemoryBudget(V5E_HBM_BYTES, name="v5e_hbm")

    @staticmethod
    def from_device(device=None) -> "MemoryBudget":
        """The attached device's memory: ``memory_stats()["bytes_limit"]``
        where the backend reports it. An accelerator that reports no limit
        is an error, not a guessed size. A CPU host (no accelerator, no
        stats) serves against the simulated v5e chip, the device the
        budget and serving tests model."""
        import jax

        dev = device or jax.devices()[0]
        stats = dev.memory_stats() or {}
        if "bytes_limit" in stats:
            return MemoryBudget(int(stats["bytes_limit"]), name=f"{dev.platform}_hbm")
        if dev.platform == "cpu":
            return MemoryBudget.v5e()
        raise ValueError(
            f"device {dev.device_kind!r} ({dev.platform}) reports no memory "
            "limit; pass the engine an explicit MemoryBudget"
        )

    # --- pricing of each strategy's peak working set ------------------------

    def _check(self, need: int, fail_type: str) -> None:
        if need > self.bytes_limit:
            raise BudgetExceeded(fail_type, need, self.bytes_limit)

    def charge_inference(self, shape, model: MeshNetConfig, dtype_bytes: int = 4) -> int:
        """Naive full-volume inference: all layer activations live (what a
        graph executor without disposal would allocate) -> the failure mode
        the paper's layer-streaming avoids."""
        vox = math.prod(shape[:3])
        layers = len(model.dilations)
        need = vox * model.channels * dtype_bytes * (layers + 1)
        need += vox * model.num_classes * dtype_bytes
        self._check(need, "full_volume_oom")
        return need

    def charge_streaming(self, shape, model: MeshNetConfig, dtype_bytes: int = 4) -> int:
        """Layer-streamed full volume: two live activations + logits."""
        vox = math.prod(shape[:3])
        need = vox * model.channels * dtype_bytes * 2
        need += vox * model.num_classes * dtype_bytes
        self._check(need, "streaming_oom")
        return need

    def charge_subvolume(self, cube: int, overlap: int, model: MeshNetConfig, dtype_bytes: int = 4) -> int:
        """Failsafe mode: one padded cube streamed + full-volume logits
        accumulated on host (as Brainchop merges into a JS array)."""
        side = cube + 2 * overlap
        need = side**3 * model.channels * dtype_bytes * 2
        need += side**3 * model.num_classes * dtype_bytes
        self._check(need, "subvolume_oom")
        return need
