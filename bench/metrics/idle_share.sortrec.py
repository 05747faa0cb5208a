"""Share of the window in which no operation ran on the device in the record
cell: ``idle_share.sort``'s reading."""

import harness

read = harness.module("metrics", "idle_share.sort").read
