"""Data substrate: paper-dataset-shaped stream generators, the paper's real
jobs 1–4 and NEXmark q17 as topologies, and the sharded token pipeline for
the LM workloads."""

from repro.data.synthetic import (
    airline_stream,
    weather_stream,
    wiki_edit_stream,
)
from repro.data.jobs import real_job_1, real_job_2, real_job_3, real_job_4
from repro.data.nexmark import nexmark_q17

__all__ = [
    "airline_stream",
    "weather_stream",
    "wiki_edit_stream",
    "real_job_1",
    "real_job_2",
    "real_job_3",
    "real_job_4",
    "nexmark_q17",
]
