"""What the round's largest program needs of the device's memory by the
compiler's account, in GB: arguments + outputs - aliased + temporaries +
code of each executable the program noted (``blades_tpu/obs/
device_phases.py``: ``hbm_need_bytes()``), the largest of them.  Unlike
``memory_peak`` it counts an executable's temporaries.  Every program's
account goes into the run's notes.  ``None`` where the program keeps no such
registry or noted nothing."""


def read(ctx, spec):
    try:
        from blades_tpu.obs import device_phases
    except ImportError:
        return None
    need = device_phases.hbm_need_bytes()
    if not need:
        return None
    ctx["notes"]["hbm_need_gb"] = {k: v / 1e9 for k, v in need.items()}
    return max(need.values()) / 1e9
