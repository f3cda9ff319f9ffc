"""Share of the traced window in which the device idled while the host
settled the sensor fleet, in %.

Device-idle time inside the window whose innermost open program span is
``fleet:*`` (`FleetMonitor.mark_all`, ``advance``, ``marker_window``),
``attrib:*`` (`attribute_block`) or ``sched:settle``
(`ContinuousBatch` settlement), over the window's length
(`bench.program_spans.fleet_idle_s`). Busy time is the same clipped
union of op intervals as ``device_idle_share`` takes, so this share is
never above it.
"""
from bench import program_spans


def read(m):
    tr = program_spans.load(m.tw.log_dir)
    if m.window_s <= 0 or not any(s.name.startswith(program_spans.FLEET) for s in tr.spans):
        return None
    return 100.0 * program_spans.fleet_idle_s(tr) / m.window_s
