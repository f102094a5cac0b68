"""Sharding: max / mean over the shards of the messages each shard was
delivered while the loop ran, the change of the service's
``shard_messages_total`` (one count per shard) from the loop's start to
its end. 1 is an even split of the edge work; a program that does not
count per shard (one chip's ``Engine``, or an older commit) reads
nothing."""


def read(run):
    if "start" not in run.counters or "end" not in run.counters:
        return None
    hi = run.counters["end"].get("shard_messages_total") or []
    lo = run.counters["start"].get("shard_messages_total") or []
    lo = list(lo) + [0] * (len(hi) - len(lo))
    delivered = [h - l for h, l in zip(hi, lo)]
    if not delivered or sum(delivered) <= 0:
        return None
    return max(delivered) / (sum(delivered) / len(delivered))
