"""Device time by the program's own phases, per traced round, in ms.

The program keeps the executables a round dispatches and says which
``blades/*`` scope each of their instructions belongs to
(``blades_tpu/obs/device_phases.py``: ``op_phases()``, ``{module:
{instruction: phase or None}}``).  Each operation of the first device inside
the traced window goes to the program launch that encloses it in time (the
trace's ``modules`` line), and takes the phase of its instruction in that
program (where the registry kept two programs of one name, the one the
launch's id names, else the one whose instructions the launch ran).  Time in which two operations overlap counts once, for the one that
started first, so that the phases and the rest sum to the device's busy
time.

The reading is the time of the metric's ``phases``; with ``"unphased":
true`` the time no phase claims: an instruction that wears no scope, and
every operation of a program the round never noted (the eager key splits,
the matrix's zero fill).  The whole split goes into the run's notes.
``None`` where the trace has no operations line, the program gives no map
(a program without the registry) or none of the phases ran."""
import bisect
import re

from pb import tracered

UNPHASED = "unphased"
NOTE = "device_phase_ms_per_round"
_ID = re.compile(r"\([^()]*\)$")   # a module event's "(id)"


def program_map():
    """The program's ``op_phases()``, or ``None`` where it has none."""
    try:
        from blades_tpu.obs import device_phases
    except ImportError:
        return None
    return device_phases.op_phases() or None


def _launches(modules):
    """``(starts, ends, names)`` of the program launches, by start."""
    mods = sorted(modules, key=lambda e: e[1])
    return ([s for _, s, _ in mods], [s + d for _, s, d in mods],
            [n for n, _, _ in mods])


def _table(module, ran, op_map) -> dict:
    """The map of a launch's program: the kept program its module event
    names exactly (on the CPU the id in it is the executable's
    fingerprint), else the one of the same name whose instructions cover
    most of the operations the launch ``ran`` (a TPU's module id is not the
    fingerprint), the newest where they tie."""
    if module in op_map:
        return op_map[module]
    base = _ID.sub("", module)
    same = [t for k, t in op_map.items() if _ID.sub("", k) == base]
    return max(same, key=lambda t: sum(n in t for n in ran), default={})


def split_ns(trace, op_map) -> dict:
    """``{phase or "unphased": ns}`` of the first device's operations inside
    the traced window; the values sum to its busy time there."""
    starts, ends, names = _launches(tracered.first_device(trace, "modules"))
    ops = sorted(tracered.first_device(trace, "ops"), key=lambda e: e[1])
    launch, ran = [], {}
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s + d / 2) - 1
        if i < 0 or s + d / 2 >= ends[i]:
            i = None
        else:
            ran.setdefault(i, set()).add(name)
        launch.append(i)
    tables = {i: _table(names[i], r, op_map) for i, r in ran.items()}
    out = {}
    cursor = float("-inf")
    for (name, s, d), i in zip(ops, launch):
        a, b = max(s, cursor), s + d
        if b <= a:
            continue
        cursor = b
        key = (tables[i].get(name) if i is not None else None) or UNPHASED
        out[key] = out.get(key, 0.0) + (b - a)
    return out


def read(ctx, spec):
    trace = ctx["trace"]
    if trace is None or not trace["devices"]:
        return None
    if not trace["devices"][min(trace["devices"])].get("ops"):
        return None   # first_device would fall back to program launches
    op_map = program_map()
    if not op_map:
        return None
    per_round = ctx["notes"].get(NOTE)   # the run's other metrics split once
    if per_round is None:
        per_round = ctx["notes"][NOTE] = dict(sorted(
            ((k, v / 1e6 / ctx["traced_rounds"])
             for k, v in split_ns(trace, op_map).items()),
            key=lambda kv: -kv[1]))
    if spec.get("unphased"):
        return per_round.get(UNPHASED, 0.0)
    found = [per_round[p] for p in spec["phases"] if p in per_round]
    return sum(found) if found else None
