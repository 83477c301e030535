"""Raylet round: share of the window's rounds that took the per-task CPU
policy (the raylet's ``queue_stats()["per_task_rounds"]`` over the
rounds), in %.  None where the raylet keeps no such counter."""


def read(record):
    stats = record["counters"]["raylet"]
    rounds = len(record["round_s"])
    if not rounds or "per_task_rounds" not in stats:
        return None
    return 100.0 * stats["per_task_rounds"] / rounds
