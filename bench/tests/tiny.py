"""A cell small enough for the CPU: the same harness, a toy deployment.

The sparse configuration's BBOB space cut to two bands of two studies
(train buckets 64 and 128, the dense path), two workers per study, a few
events a second; and a sparse variant with one study just past
``SPARSE_THRESHOLD``."""

import copy

from bench.lib import cells


def tiny_cell(kind: str = "dense", rate: float = 3.0):
    base = cells.load_cell("sparse.steady")
    config = copy.deepcopy(base.config)
    if kind == "dense":
        config["studies"]["bands"] = [
            {"count": 2, "size_min": 40, "size_max": 44, "bucket": 64},
            {"count": 2, "size_min": 80, "size_max": 90, "bucket": 128}]
        config["workers_per_study"] = 2
        traffic = dict(base.traffic, rate_per_s=rate,
                       count={"values": [1, 2, 4], "weights": [0.4, 0.3, 0.3]})
    else:
        config["studies"]["bands"] = [
            {"count": 1, "size_min": 1100, "size_max": 1100, "bucket": 2048}]
        config["workers_per_study"] = 2
        traffic = dict(base.traffic, rate_per_s=rate,
                       count={"values": [2], "weights": [1.0]})
    config["server"] = dict(config["server"], n_pythia_workers=2)
    return cells.Cell(name=base.name, config_name=base.config_name,
                      config=config, traffic_name=base.traffic_name,
                      traffic=traffic, chips=1, end_to_end=base.end_to_end,
                      per_layer=base.per_layer)
