"""Whole decode step: its least time (bytes or operations) over its time."""

from bench.shares import step_roofline as read
