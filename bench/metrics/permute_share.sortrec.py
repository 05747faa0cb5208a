"""Share of device busy time under the program's ``repro.kv_permute`` scope in
the record cell: the sorts that invert the order and put each record word in
it. ``permute_share.sortkv``'s reading."""

import harness

read = harness.module("metrics", "permute_share.sortkv").read
