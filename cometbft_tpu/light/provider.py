"""Light block providers (reference: light/provider/provider.go, mock, http).

A Provider serves LightBlocks for a chain and accepts evidence reports. The
HTTP provider rides the JSON-RPC client (rpc/client/http.py) against a full
node's /commit + /validators routes."""

from __future__ import annotations

from cometbft_tpu.libs import trace
from cometbft_tpu.types.block import SignedHeader
from cometbft_tpu.types.light_block import LightBlock
from cometbft_tpu.types.validator_set import ValidatorSet


class ErrLightBlockNotFound(Exception):
    """provider.ErrLightBlockNotFound: requested height unavailable."""


class ErrNoResponse(Exception):
    """provider.ErrNoResponse: provider unreachable/misbehaving."""


class Provider:
    """light/provider/provider.go Provider interface."""

    # Bytes read from the provider's source so far, where it counts them
    # (None: it does not); `fetch` puts a fetch's share on its span.
    bytes_received: int | None = None

    def chain_id(self) -> str:
        raise NotImplementedError

    def light_block(self, height: int) -> LightBlock:
        """Height 0 means latest. Raises ErrLightBlockNotFound/ErrNoResponse."""
        raise NotImplementedError

    def report_evidence(self, ev) -> None:
        raise NotImplementedError


def fetch(provider: Provider, height: int) -> LightBlock:
    """`provider.light_block(height)` under a `light.fetch` span: every
    fetch of the light client, the decode of what arrived included."""
    with trace.span("light.fetch", height=height) as span:
        before = getattr(provider, "bytes_received", None)
        lb = provider.light_block(height)
        if before is not None:
            span.set(bytes=provider.bytes_received - before)
    return lb


class MockProvider(Provider):
    """light/provider/mock/mock.go: canned LightBlocks by height."""

    def __init__(self, chain_id: str, light_blocks: dict[int, LightBlock]):
        self._chain_id = chain_id
        self.light_blocks = dict(light_blocks)
        self.evidences = []

    def chain_id(self) -> str:
        return self._chain_id

    def light_block(self, height: int) -> LightBlock:
        if height == 0:
            if not self.light_blocks:
                raise ErrLightBlockNotFound("no blocks")
            height = max(self.light_blocks)
        lb = self.light_blocks.get(height)
        if lb is None:
            raise ErrLightBlockNotFound(f"no light block at height {height}")
        return lb

    def report_evidence(self, ev) -> None:
        self.evidences.append(ev)


class BlockStoreProvider(Provider):
    """Node-local provider over the block + state stores — the light
    gateway's source when it runs inside a node (no RPC round trip, no
    JSON re-encode).  Commit selection mirrors the /commit route: the tip
    serves its seen commit, history serves the canonical block commit."""

    def __init__(self, chain_id: str, block_store, state_store):
        self._chain_id = chain_id
        self._block_store = block_store
        self._state_store = state_store

    def chain_id(self) -> str:
        return self._chain_id

    def base_height(self) -> int:
        """Lowest retained height — the gateway refuses MMR proof serving
        when the store is pruned above 1 (leaf index = height - 1)."""
        return self._block_store.base()

    def header_hash(self, height: int) -> bytes | None:
        """Header hash without materializing the validator set (the MMR
        append path touches every height once)."""
        meta = self._block_store.load_block_meta(height)
        return meta.header.hash() if meta is not None else None

    def light_block(self, height: int) -> LightBlock:
        tip = self._block_store.height()
        h = height if height > 0 else tip
        meta = self._block_store.load_block_meta(h)
        if meta is None:
            raise ErrLightBlockNotFound(f"no block meta at height {h}")
        if h == tip:
            commit = self._block_store.load_seen_commit(h)
        else:
            commit = self._block_store.load_block_commit(h)
        if commit is None:
            raise ErrLightBlockNotFound(f"no commit at height {h}")
        vals = self._state_store.load_validators(h)
        if vals is None:
            raise ErrLightBlockNotFound(f"no validators at height {h}")
        return LightBlock(
            signed_header=SignedHeader(meta.header, commit), validator_set=vals
        )

    def report_evidence(self, ev) -> None:
        pass  # a node-local source has nowhere meaningful to forward this


class HTTPProvider(Provider):
    """light/provider/http/http.go: LightBlocks from a node's RPC."""

    def __init__(self, chain_id: str, rpc_client):
        self._chain_id = chain_id
        self.client = rpc_client

    def chain_id(self) -> str:
        return self._chain_id

    def light_block(self, height: int) -> LightBlock:
        h = height if height > 0 else None
        try:
            commit_res = self.client.commit(h)
            actual_h = int(commit_res["signed_header"]["header"]["height"])
            vals = self._validators_all(actual_h)
        except (ErrLightBlockNotFound, ErrNoResponse):
            raise
        except Exception as e:
            raise ErrNoResponse(str(e)) from e
        sh = _signed_header_from_json(commit_res["signed_header"])
        lb = LightBlock(signed_header=sh, validator_set=vals)
        lb.validate_basic(self._chain_id)
        return lb

    def _validators_all(self, height: int) -> ValidatorSet:
        """Page through /validators (http.go:165)."""
        from cometbft_tpu.types.validator import Validator

        vals = []
        page = 1
        while True:
            res = self.client.validators(height, page=page, per_page=100)
            for v in res["validators"]:
                vals.append(_validator_from_json(v))
            total = int(res["total"])
            if len(vals) >= total or not res["validators"]:
                break
            page += 1
        if not vals:
            raise ErrLightBlockNotFound(f"no validators at height {height}")
        return ValidatorSet(vals)

    def report_evidence(self, ev) -> None:
        self.client.broadcast_evidence(ev)


def _validator_from_json(v: dict):
    import base64

    from cometbft_tpu.crypto.encoding import pub_key_from_type_and_bytes
    from cometbft_tpu.types.validator import Validator

    pk = v["pub_key"]
    pub = pub_key_from_type_and_bytes(pk["type"], base64.b64decode(pk["value"]))
    val = Validator.new(pub, int(v["voting_power"]))
    val.proposer_priority = int(v.get("proposer_priority", 0))
    return val


def _signed_header_from_json(d: dict) -> SignedHeader:
    from cometbft_tpu.rpc.json_codec import signed_header_from_json

    return signed_header_from_json(d)
