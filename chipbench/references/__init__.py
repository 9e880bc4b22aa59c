"""Plain references of deployment kinds: ``<kind>.py`` plans a config of
that kind in numpy, importing nothing of the program, in the interface
of ``chipbench.reference`` that ``chipbench.check`` and
``chipbench.control`` call (``deployment``, ``plan``,
``constraint_excess``). A config that names no kind is checked by
``chipbench.reference``."""
