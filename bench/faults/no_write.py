"""A group commit that acknowledges and writes nothing: the store's state
unchanged by a step."""

import faults


def make(orig):
    def commit_batch(self, shard_id, entries):
        return []
    return commit_batch


def apply():
    return faults.patch("repro.core.store", "ShardedPromptStore.commit_batch",
                        make)
