"""Energy the cooling source took from the gas over the window's verified
steps, as a share of ``|etot|`` at the window's first verified step:
``-sum(e_cool_step)`` over the window's ``numerics`` events (schema v16: the
program's radiated-energy counter, negative where the gas radiates) over the
first ``etot`` of the window's ``physics`` events. ``energy_drift`` minus this
share is the drift the run would read if cooling were bookkept. A count,
never a speed; nothing where the program reports no such field."""


def read(run):
    steps = [v for e in run["events"] if e["kind"] == "numerics"
             for v in e.get("e_cool_step") or ()]
    etot = [e["etot"][0] for e in run["events"]
            if e["kind"] == "physics" and e.get("etot")]
    if not steps or not etot or not etot[0]:
        return None
    return -sum(steps) / abs(etot[0])
