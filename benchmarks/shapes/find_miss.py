"""GET /api/traces/{id} for a random id no block holds: 404, never a 200."""
KIND = "find"


def build(rnd, env, params):
    return {"id": rnd.getrandbits(128).to_bytes(16, "big").hex()}


def request(op, env):
    return "GET", f"/api/traces/{op['id']}", None, {}


def check(op, status, body, env):
    return status == 404, "" if status == 404 else f"HTTP {status} for a miss"
