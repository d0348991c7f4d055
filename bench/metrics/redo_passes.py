"""redo_passes: level passes the traced fit did twice: its M escalations
plus its levels that retried materialization (``LevelStats``).
Layer: mining loop."""


def read(x):
    if not x.stats:
        return None
    return sum(s["escalations"] + int(s["retried"]) for s in x.stats[-1])
