"""The driver's check-window planner as a pure function (no jax, no chip).

``SkinTrend`` folds the ``(list_slack, dt)`` every verified step carries
and plans the next window: the rate, how many further steps the list
covers, and the window's length. Each case below is a synthetic flow (skin
used per unit of time, the steps' ``dt``) run through a model of the
driver's loop: plan, launch the planned steps, fold what they found,
rebuild where the plan covers nothing. The model asserts what the driver
relies on: a launched step never finds an expired list once a trend is
known.
"""

import pytest

from sphexa_tpu.simulation import _LIST_COVER_MARGIN, SkinTrend, WindowPlan

MAX_DT_INCREASE = 1.1


def _run(check_every, use_per_time, dt_of, steps, lists=True):
    """Model of Simulation.step/flush/_settle on a flow that uses
    ``use_per_time`` of the skin per unit of time. Returns the plans made
    at every boundary, the windows' lengths, the ages of the lists retired
    and the least slack any launched step found."""
    trend, used, age, it = SkinTrend(), 0.0, 0, 0
    plans, windows, ages, least = [], [], [], 1.0
    while it < steps:
        plan = trend.plan(check_every)
        if plan.cover == 0 and trend.slack is not None:
            ages.append(age)
            used, age, trend = 0.0, 0, trend.rebuilt()
            plan = trend.plan(check_every)
        plans.append(plan)
        for _ in range(plan.steps):
            slack, dt = 1.0 - used, dt_of(it)
            least = min(least, slack)
            trend = trend.observe(slack if lists else None, dt,
                                  MAX_DT_INCREASE)
            used += use_per_time * dt
            age, it = age + 1, it + 1
        windows.append(plan.steps)
    return plans, windows, ages, least


def _flat(_it):
    return 1.0


def _ramp(it):
    return 1.1 ** it


def _falling(it):
    return 0.99 ** it


#: name -> (check_every, skin used per unit of time, dt(it), lists on,
#:          steps run, windows expected from the first one, ages expected
#:          of the lists retired, first plan with a trend as (rate, cover,
#:          steps) or None)
CASES = {
    # Noh at 1.1M while dt >= 3.8e-4 (PERF.md, PR 25): a list lives 7
    # steps. Slack 0.55 at step 4: three more steps, rebuild at age 7.
    "noh-7-steps": (4, 0.15, _flat, True, 28,
                    [4, 3, 4, 3, 4, 3, 4, 3], [7, 7, 7],
                    (0.15, 3, 3)),
    # Noh once dt < 3.7e-4: 0.111 per step, slack 0.22 at the eighth.
    # The margin must not cost it its ninth step.
    "noh-9-steps": (4, 1.0 / 9.0, _flat, True, 27,
                    [4, 4, 1, 4, 4, 1, 4, 4, 1], [9, 9],
                    (1.0 / 9.0, 5, 4)),
    # Sedov-like: 0.03 per step. Whole windows; the old fixed rule
    # (rebuild under slack 0.25 at a boundary) retired this list at age
    # 28, the first boundary past step 26 (slack 0.22). The plan keeps it
    # to age 32 (slack 0.07, the next step would find 0.04): later than
    # the old rule, never earlier.
    "sedov-whole-windows": (4, 0.03, _flat, True, 40,
                            [4] * 10, [32],
                            (0.03, 28, 4)),
    # every step checked: the horizon is one step, the life the same 7
    "check-every-1": (1, 0.15, _flat, True, 15,
                      [1] * 15, [7, 7],
                      (0.15, 5, 1)),
    # lists off (mesh, nbody, block-dt, ``use_lists=False``; one-chip
    # gravity walks lists since PR 44): steps carry no list_slack, the
    # planner is inert, windows are whole
    "lists-off": (4, 0.15, _flat, False, 16, [4, 4, 4, 4], [], None),
    # dt grows by the limiter's 1.1 per step, so each step uses 10 % more
    # skin than the one before: the estimate must not under-read it
    "dt-ramp": (4, 0.05, _ramp, True, 24, None, None, None),
    # a falling dt is not bet on: predictions err to the safe side
    "dt-falling": (4, 0.15, _falling, True, 30, None, None, None),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_planned_windows(name):
    check_every, use, dt_of, lists, steps, windows, ages, first = CASES[name]
    plans, got_windows, got_ages, least = _run(check_every, use, dt_of,
                                               steps, lists)
    # no estimate yet (a run's first list): the whole window, rollback is
    # the net
    assert plans[0] == WindowPlan(None, None, check_every)
    assert all(1 <= p.steps <= check_every for p in plans)
    if not lists:
        assert all(p == plans[0] for p in plans)
    else:
        # with a trend, no launched step finds an expired list, and none
        # but a list's last finds less than the margin
        assert least >= 0.0, least
        with_trend = [p for p in plans if p.rate is not None]
        assert with_trend and all(p.cover >= 1 for p in with_trend)
    if windows is not None:
        assert got_windows[:len(windows)] == windows
        assert got_ages[:len(ages)] == ages and len(got_ages) >= len(ages)
    if first is not None:
        rate, cover, planned = first
        got = next(p for p in plans if p.rate is not None)
        assert got.rate == pytest.approx(rate, rel=1e-6)
        assert (got.cover, got.steps) == (cover, planned)


def test_ramp_is_not_under_read():
    """On a 1.1x dt ramp the rate a plan is made with is at least what
    the next step really uses, and lists still live several windows."""
    use = 0.05
    plans, windows, ages, least = _run(4, use, _ramp, 24)
    it = 0
    for plan, n in zip(plans, windows):
        if plan.rate is not None:
            assert plan.rate >= use * _ramp(it) * (1 - 1e-9), (it, plan)
        it += n
    assert least >= _LIST_COVER_MARGIN * 0.5
    assert ages and min(ages) >= 4


def test_trend_survives_a_rebuild_and_a_fresh_list_has_slack_one():
    t = SkinTrend()
    for slack in (1.0, 0.85, 0.70, 0.55):
        t = t.observe(slack, 2e-4, MAX_DT_INCREASE)
    assert t.plan(4) == WindowPlan(pytest.approx(0.15), 3, 3)
    fresh = t.rebuilt()
    assert fresh.rate == t.rate and fresh.slack is None
    # 1 - 0.15 j >= margin for the j = 0..6 steps after the first: 7 steps
    assert fresh.plan(4) == WindowPlan(pytest.approx(0.15), 7, 4)
    # the first step of the new list reads slack 1 and teaches nothing new
    again = fresh.observe(1.0, 2e-4, MAX_DT_INCREASE)
    assert again.rate == t.rate and again.plan(4).cover == 6
    # a flow at rest (or a slack that rose) gives no trend: whole windows
    still = again.observe(1.0, 2e-4, MAX_DT_INCREASE)
    assert still.plan(4) == WindowPlan(None, None, 4)
