"""The paper's DCGAN (arXiv:2107.08681 Sec. IV; arXiv:1511.06434)."""
