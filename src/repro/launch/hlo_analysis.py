"""What a compiled step holds: its memory analysis and its Pallas kernels."""
from __future__ import annotations

import re
from typing import Dict


def extract_memory(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


_KERNEL_RE = re.compile(r'op_name="(?:[^"]*/)?([^"/]+)/pallas_call"')


def pallas_kernels(hlo_text: str) -> Dict[str, int]:
    """{kernel name: count} of the compiled Pallas calls in a TPU program.

    Each kernel is a ``tpu_custom_call`` whose op metadata ends in
    ``<name>/pallas_call`` (the ``name=`` every kernel under
    ``repro.kernels`` passes). Off the TPU the kernels are interpreted and
    none appear.
    """
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = _KERNEL_RE.search(line)
            name = m.group(1) if m else "unnamed"
            out[name] = out.get(name, 0) + 1
    return out
