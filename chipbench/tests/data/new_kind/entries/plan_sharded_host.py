"""A caller that holds its fleet's link gains on the host: one
``plan_sharded`` of the cell's fleet from gains copied off the device."""
import numpy as np

from chipbench.cell import fetch_plan as fetch  # noqa: F401


def call(cell, gains):
    return cell.planner.plan_sharded(cell.spec, cell.plan_scenario,
                                     gains=np.asarray(gains), mesh=cell.mesh)
