"""On-chip benchmark of the VFL coreset system: one cell per run.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
"""
