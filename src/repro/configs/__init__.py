from .registry import ARCH_IDS, LLAMA_PAPER, get_arch
__all__ = ["ARCH_IDS", "LLAMA_PAPER", "get_arch"]
