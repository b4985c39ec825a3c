"""Max over min, over the devices, of self time under the first phases
``gravity-mac`` + ``gravity-m2p`` + ``gravity-p2p`` in the traced stretch
(stage_times.py): the slabs' imbalance in the tree solve's own work. The busy
union cannot show it: a device waiting in a collective is busy. Nothing on one
device."""

import stage_times

PHASES = ("gravity-mac", "gravity-m2p", "gravity-p2p")


def read(run):
    return stage_times.imbalance(run, PHASES)
