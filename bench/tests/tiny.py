"""Cells of the benchmark shrunk to a size the CPU runs in seconds:
three edge servers of two devices (or of six clients, two a round),
batch 4, and the model's and the traffic's own tiny sizes (``TINY`` in
``bench/models/<model>.py`` and ``bench/traffic/<generator>.py``)."""
import copy
import dataclasses
from typing import Union

from bench import harness


def tiny_cell(cell: Union[str, harness.Cell]) -> harness.Cell:
    """``cell`` (a cell of ``BENCHMARK.json`` by name, or a resolved
    cell) at the CPU's size."""
    if isinstance(cell, str):
        cell = harness.resolve(cell)
    cfg = copy.deepcopy(cell.config)
    cfg["model_sizes"].update(harness.load_module("models",
                                                  cfg["model"]).TINY)
    cfg["federation"].update(num_clusters=3, devices_per_cluster=2)
    cfg["optimizer"].update(batch=4)
    if "population" in cfg:
        cfg["population"].update(clients_per_cluster=6, cohort_per_cluster=2)
    traffic = copy.deepcopy(cell.traffic)
    traffic["data"].update(harness.load_module(
        "traffic", traffic["generator"]).TINY)
    return dataclasses.replace(cell, config=cfg, traffic=traffic)
