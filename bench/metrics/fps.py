"""fps: frames whose logits reached the host inside the window, over the
window's seconds (host clock)."""


def read(run):
    rec = run.record
    done = sum(c["n"] for c in rec.calls
               if c.get("ok") and c["done"] <= rec.end)
    return done / rec.seconds
