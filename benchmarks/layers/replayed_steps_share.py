"""Share of the window's launched steps that were a rolled-back window's
(discarded, then replayed as checked steps): Σ ``rollback.steps`` ÷
attempted, in percent. ``None`` on a program without the v10 list events."""

import list_lifecycle


def read(run):
    attempted = run["window"]["attempted"]
    if not list_lifecycle.rebuilds(run["events"]) or not attempted:
        return None
    return 100.0 * list_lifecycle.replayed_steps(run["events"]) / attempted
