"""Whole decode step: operations per second over the bf16 peak."""

from bench.shares import step_mfu as read
