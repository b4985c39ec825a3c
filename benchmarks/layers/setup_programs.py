"""``compile`` events before the window: programs traced, lowered and
compiled or loaded, eager ``jnp`` ops among them."""

import startup_spans


def read(run):
    return startup_spans.leaf(run, "programs")
