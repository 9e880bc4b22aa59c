"""Traffic entries: ``<entry>.py`` gives ``call(cell, gains)``, the
planner's result for one request's link gains, and ``fetch(result)``,
its ``chipbench.cell.Answer`` on the host, for a traffic file that names
``"entry": "<entry>"``. ``plan_sharded`` is built into
``chipbench.cell``."""
