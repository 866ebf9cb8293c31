"""Cells of the benchmark shrunk to a size the CPU runs in seconds:
8x8 images, three edge servers of two devices (or of six clients, two a
round), batch 4. The CNN's channel and hidden widths stay published."""
import copy
import dataclasses

from bench import harness


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.resolve(name)
    cfg = copy.deepcopy(cell.config)
    cfg["model_sizes"].update(image=8)
    cfg["federation"].update(num_clusters=3, devices_per_cluster=2)
    cfg["optimizer"].update(batch=4)
    if "population" in cfg:
        cfg["population"].update(clients_per_cluster=6, cohort_per_cluster=2)
    traffic = copy.deepcopy(cell.traffic)
    traffic["data"].update(train_samples=128, test_samples=16,
                           samples_per_device=16)
    return dataclasses.replace(cell, config=cfg, traffic=traffic)
