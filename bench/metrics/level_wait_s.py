"""level_wait_s: the sum of the traced fit's ``LevelStats.map_seconds``,
each from a level program's dispatch until its wire is fetched and
decoded (host clock, program span).  Layer: level program."""


def read(x):
    if not x.stats:
        return None
    return sum(s["map_seconds"] for s in x.stats[-1])
