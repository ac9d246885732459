"""How the trace reduction recognises the program's kernels: a Pallas
kernel's custom call carries the name of the jitted wrapper in
``repro/kernels/ops.py`` that made it (``tezo_perturb.12``,
``vmap_jit_tezo_adam_update__.6``, ``flash_attention.10``).  A wrapper
renamed in the program is lost here until this table follows it; its
metrics then read nothing and the traced run fails."""
import re

# the ZO weight passes: TeZO perturb/bridge/update, dense-noise passes
ZO_PASS = re.compile(r"tezo_perturb|tezo_adam_update|noise_perturb|"
                     r"noise_update")
PAGED_DECODE = re.compile(r"paged_decode_attention")


def matcher(pattern):
    return lambda text: pattern.search(text) is not None
