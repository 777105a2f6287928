"""A group commit that writes the first half of its records and
acknowledges all of them."""

import faults


def make(orig):
    def commit_batch(self, shard_id, entries):
        entries = list(entries)
        return orig(self, shard_id, entries[: (len(entries) + 1) // 2])
    return commit_batch


def apply():
    return faults.patch("repro.core.store", "ShardedPromptStore.commit_batch",
                        make)
