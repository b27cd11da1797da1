"""Mean ``dispatch_ms`` of the window's ``step`` events (the program's own
StepTimer; the event log is on only in the traced run)."""


def read(run):
    vals = [e["dispatch_ms"] for e in run["events"] if "dispatch_ms" in e]
    return sum(vals) / len(vals) if vals else None
