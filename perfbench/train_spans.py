"""The traced train steps, reduced in the worker to what the readers need
(the interval lists stay there: a report carries numbers)."""

from __future__ import annotations

from perfbench import xplane


def summarise(reduced: dict, program: str) -> dict:
    """``steps_span_s``: first traced step's start to the last one's end, by
    the benchmark's ``train.step#k`` annotations (else the device's own first
    and last operation). Per device, inside that span: busy seconds,
    collective self seconds, and seconds per execution of ``program``; and
    the result line's three figures over the same span (``device_window``)."""
    ann = [a for a in reduced.get("annotations", [])
           if a[0].startswith(xplane.ANNOTATION_PREFIX + "train.step#")]
    if not reduced.get("busy_s"):
        return {"n_devices": 0, "n_steps": len(ann)}
    span = ((min(a[1] for a in ann), max(a[2] for a in ann)) if ann
            else tuple(reduced["span_ns"]))
    busy = [xplane.busy_inside_s(iv, span)
            for iv in reduced["all_busy_intervals"]]
    step_s = []
    for mods in reduced["modules_by_device"]:
        times = [t for name, ts in mods.items() if program in name
                 for t in ts]
        step_s.append(sum(times) / len(times) if times else None)
    return {
        "n_devices": reduced["n_devices"], "n_steps": len(ann),
        "from_annotations": bool(ann),
        "steps_span_s": (span[1] - span[0]) / 1e9,
        "busy_in_span_s": busy, "busy_s": reduced["busy_s"],
        "device_window": xplane.device_window(reduced, span),
        "collective_s": reduced["collective_s"],
        "step_device_s": step_s,
        "n_executions": [sum(len(ts) for name, ts in mods.items()
                             if program in name)
                         for mods in reduced["modules_by_device"]],
        "top_ops": xplane.top_ops(reduced),
        "idle_gaps": xplane.idle_gaps_by_span(
            reduced, [], span, "train.host"),
    }
