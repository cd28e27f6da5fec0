"""commit_exchange_kb.burst4chip: what one shard receives through the
sharded sparse solve's commit collectives per burst: on each ``shard_commit``
span, ``commit_bytes_per_round`` x ``reconcile_rounds``, summed per cycle,
mean over the window's cycles (program_counter, KiB). None where no span
carries both counters."""

from stages import _per_cycle


def exchanged(args):
    per_round = args.get("commit_bytes_per_round")
    rounds = args.get("reconcile_rounds")
    if per_round is None or rounds is None:
        return None
    return per_round * rounds


def read(run):
    found = _per_cycle(run, ("shard_commit",))
    if found is None:
        return None
    n, picked = found
    values = [exchanged(args) for _, _, args in picked]
    values = [v for v in values if v is not None]
    if not values:
        return None
    return sum(values) / n / 1024
