"""map_kernel_roofline (%; kernels, device trace): the least time HBM needs
for the int32 coordinates the window's map launches were asked for (the
widest query of each launch, dim x 4 bytes a point) over the device time of
the map-kernel ops.  Bound by HBM bandwidth: the maps are integer work on
the vector unit, for which the chip publishes no peak.

The kernel is found by name: the Pallas kernel is ``_map_kernel``, and the
program compiled for a v5e holds it as the custom call
``tpu_custom_call.<n>`` (the trace's op name, shortened by ``trace.op_name``
to ``tpu_custom_call.1 s32[8,2097152]``).  The evaluate cells run no other
custom call."""
from bench import work


def is_map_kernel(name: str) -> bool:
    return "map_kernel" in name or "tpu_custom_call" in name


def read(run):
    device_s = run.device_seconds(is_map_kernel)
    if device_s is None:
        return None
    need = sum(work.evaluate_bytes(l["points"], l["dim"])
               for _, _, _, info in run.spans("evaluate_batch")
               for l in info["launches"])
    if not need:
        return None
    return need / run.peaks["hbm_bytes_per_s"] / device_s * 100.0
