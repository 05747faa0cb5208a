"""Share of device busy time under the program's ``repro.kv_order`` scope in
the record cell: the one stable sort of the three key words and the index
that gives the records' order. ``order_share.sortkv``'s reading."""

import harness

read = harness.module("metrics", "order_share.sortkv").read
