"""Executed plan: each call's operations at its own precision's peak,
summed over the step, over the time a step took."""

from bench.shares import plan_mfu as read
