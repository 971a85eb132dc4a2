"""Share of the traced window in which no operation ran on the device."""

from bench.shares import idle_share as read
