"""Operations and bytes of one call of the serve step: what each family's
``serve_step(conf)`` (``bench/reference/<reference>.py``) returns, from
the shapes of a configuration file, and the rules its count keeps.

It returns ``flops``, ``bytes``, ``weight_bytes`` and ``cache_bytes``,
each the least the step must compute or move at the shape
it is compiled for: every weight the step reads at its stored dtype, the
state it reads (a cache row is read whether in use or not), and the
operations of its matrix products and attention. What it writes (one
cache slot per row) and its small element-wise work are left out, so the
roofline share the count gives cannot pass 100%. A step that stops
reading the whole shape (a ragged or paged cache) needs its family's
count revisited first.
"""

from __future__ import annotations

#: bytes of one element of each dtype a configuration may name
BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}
