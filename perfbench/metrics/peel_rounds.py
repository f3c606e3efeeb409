"""Frontier peel rounds per job (``PeelStats.rounds``), a count."""


def read(run):
    rounds = [j.counters.rounds for j in run.completed
              if hasattr(j.counters, "max_frontier")]
    return sum(rounds) / len(rounds) if rounds else None
