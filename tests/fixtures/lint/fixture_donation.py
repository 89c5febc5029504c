"""Seeded violations for the donation-safety checker (never executed)."""

from repro.kernels.lbm_collide.ops import make_fused_superstep


def use_after_donate(pdfs, cfg):
    fn = make_fused_superstep(**cfg)
    fn(pdfs)
    return pdfs[0].sum()  # TP-DONATED: pdfs was consumed by the donating program


def alias_after_donate(pdfs, cfg):
    fn = make_fused_superstep(**cfg)
    stash = pdfs
    fn(pdfs)
    return stash  # TP-ALIAS: stash aliases the donated buffer


def attribute_stash(holder, pdfs, cfg):
    fn = make_fused_superstep(**cfg)
    holder.saved = pdfs
    fn(pdfs)
    return holder.saved  # TP-ATTR: attribute alias of the donated buffer


def safe_rebind(pdfs, cfg):
    fn = make_fused_superstep(**cfg)
    pdfs = fn(pdfs)  # NEG-REBIND: the sanctioned ping-pong idiom
    return pdfs


def sanctioned_read(pdfs, cfg):
    fn = make_fused_superstep(**cfg)
    fn(pdfs)
    # repro: donation-ok(fixture: exercises the allowlist annotation)
    return pdfs  # NEG-ANNOTATED: allowlisted


def with_block_rebind(pdfs, cfg, span):
    fn = make_fused_superstep(**cfg)
    with span:
        pdfs = fn(pdfs)  # NEG-WITH-REBIND: revive must work inside a with suite
        total = pdfs[0]
    return total


def with_block_use_after_donate(pdfs, cfg, span):
    fn = make_fused_superstep(**cfg)
    with span:
        fn(pdfs)
        return pdfs[0]  # TP-WITH: read after donate inside the with suite
