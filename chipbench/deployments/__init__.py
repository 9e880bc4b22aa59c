"""Deployment kinds, the program's side: ``<kind>.py`` gives
``device_specs(config) -> tuple[DeviceSpec, ...]``, one per entry of the
config's ``groups`` in order, for a config that names ``"kind": "<kind>"``
(``chipbench.cell.device_specs``). A config that names no kind is the
paper's table columns, built in ``chipbench.cell``."""
