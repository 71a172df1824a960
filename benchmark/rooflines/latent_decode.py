"""``ops/pallas/latent_attention.py``: one token a live row against the
row's cached latents, each fetched once for keys and values alike.

What the mathematics needs, whatever implements it (NOT the op's
shapes: whole blocks, or a leaf padded to whole lanes, move more bytes
for the same work and must read a LOWER share, never one above 100%).
Per LIVE cached token of a row (its window, the new token with it):

- bytes: the token's latent, ``latent + rope`` numbers (512 + 64) in
  bfloat16, once;
- operations: for each head a score over ``latent + rope`` and a
  weighted sum over ``latent``, a multiply-and-add (2) each.

Live tokens a call are the program's own count over the run
(``ctx["latent_tokens_per_call"]``).  On a v5e the step's 60 operations
a byte lie under the ridge (240): bound by bytes."""


def match(op: str) -> bool:
    return op.split(" = ")[0].startswith("%latent_decode")


def cost(op: str, ctx):
    d = ctx["latent_dims"]
    tokens = float(ctx["latent_tokens_per_call"])
    wide = d["latent"] + d["rope"]
    flops = tokens * d["heads"] * (wide + d["latent"]) * 2.0
    return flops, tokens * wide * 2
