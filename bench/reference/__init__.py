"""The benchmark's plain references, which import nothing of the program.

``types``, ``fitness``, ``greedy``, ``dspot`` and ``burst`` are copies of
the program's modules of the same role (``repro.core``) at the commit
that added the benchmark, so that a later change to the program cannot
move the yardstick.  ``plans`` evaluates an allocation by the batched
ILS's Eq. 8 bound in numpy, and ``ils`` runs the batched ILS itself as a
numpy loop.
"""
