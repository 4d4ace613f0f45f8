"""Simple-HGN (Lv et al. 2021, arXiv:2112.14936) as the program runs it:
RGAT's attention NA with an edge-type bias, ``edge_emb[i] . a_edge`` for
the i-th metapath, added to every logit.  Its work counts are RGAT's."""
from __future__ import annotations

from chipbench.params import small
from chipbench.spec import load_model

_rgat = load_model("rgat")
program_config = _rgat.program_config
draw_na = _rgat.draw_na
na_flops = _rgat.na_flops
na_kernel_calls = _rgat.na_kernel_calls


def draw_layer(keys, cfg, mps):
    emb = int(cfg["edge_emb_dim"])
    return {"edge_emb": small(next(keys), len(mps), emb), "a_edge": small(next(keys), emb)}


def na(dot, lp, i, mp, hs, h_dst, src, dst, n_dst):
    logit = _rgat.logits(dot, lp["na"][mp], hs, h_dst, src, dst)
    logit = logit + dot(lp["edge_emb"][i][None, :], lp["a_edge"][:, None])[0, 0]
    return _rgat.attend(logit, hs, src, dst, n_dst)
