"""Share of the window's wall the driver spent outside its launch-to-fetch
spans: 1 - sum(window.wall_s) / (wall - the harness's dump and profiler
start/stop spans). List rebuilds, reconfigure sizing and the per-window
host work live here."""

import windows


def read(run):
    wall = run["window"]["wall_s"] - windows.span_seconds(
        run["spans"], "dump", "trace-start", "trace-stop")
    if wall <= 0:
        return None
    return 100.0 * (1.0 - windows.device_span_seconds(run["events"]) / wall)
