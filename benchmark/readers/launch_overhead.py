"""Host wall a launch costs beyond its device time, in milliseconds:
the mean ``batch_verify`` span of the program's ring (the ``last``
newest) less the mean device time of the programs matching
``params["pattern"]`` in the traced slice.  The program's
``DeviceUsage`` (``crypto/health.py``) books the whole of that wall as
device-busy on the host's clock; this is by how much a launch
overstates it.  None without a device plane or under
``span_ms.MIN_PER`` launches in the ring."""

from benchmark.readers import program_ms, span_ms

SPAN = "batch_verify"


def read(ctx: dict, params: dict) -> float | None:
    device_ms = program_ms.read(ctx, params)
    if device_ms is None:
        return None
    wall_ms = span_ms.per_item_ms(span_ms.ring(), [SPAN], SPAN,
                                  int(params.get("last", 200)))
    return None if wall_ms is None else wall_ms - device_ms
