"""The NEXmark event stream (Tucker, Tufte, Papadimos, Maier: "NEXMark - a
benchmark for queries over data streams"), as the Apache Beam suite's
generator makes it at its defaults (``NexmarkConfiguration`` /
``GeneratorConfig``): three event types interleaved in one stream,
Person : Auction : Bid = 1 : 3 : 46 of every 50 events, of average sizes
200 / 500 / 100 B, with hot auctions, sellers and bidders (ratios 2 / 4 / 4),
prices ``round(10 ** (6u) * 100)`` cents and epoch-millisecond timestamps.

Event number ``n = p * records_per_partition + i`` picks the type by
``n mod 50`` (0: Person, 1-3: Auction, 4-49: Bid) and every id the way
Beam's ``lastBase0PersonId`` / ``lastBase0AuctionId`` do; ``dateTime =
base_ms + n`` (one event a millisecond), so a reference recovers ``n`` from
any event it keeps. A value is flat compact ASCII JSON with no escapes,
``event_type`` first (0 / 1 / 2, the Flink suite's numbering), then the
type's own fields in the Beam model's order:

- Bid: ``auction``, ``bidder``, ``price``, ``dateTime``, ``extra``
- Auction: ``id``, ``itemName``, ``description``, ``initialBid``,
  ``reserve``, ``dateTime``, ``expires``, ``seller``, ``category``, ``extra``
- Person: ``id``, ``name``, ``emailAddress``, ``creditCard``, ``city``,
  ``state``, ``dateTime``, ``extra``

``extra`` pads a value toward its type's average size as Beam's
``nextExtra`` does: nothing when the other fields are already over it,
else the shortfall +-20%.

``assumed`` (the suites ship Java coders and SQL rows, not this flat JSON,
and nothing can be fetched here, so what is not recalled exactly is set
here and listed in the configuration's ``assumed``): the JSON encoding and
key order; sizes counted in JSON bytes (Beam counts its coder's 8 B a
long); strings are seeded lowercase letters cut from one pool a partition
(Beam's ``nextString`` also draws spaces); the name, city and state lists;
``expires`` = ``dateTime`` + 1..3,332 ms (Beam's ``nextAuctionLengthMs``
at 100 auctions in flight, one event a millisecond); ``rint`` for
``Math.round``; one generator state a partition, ``[seed, p]``.

Imports nothing of the program and nothing of ``docs.py``.
"""

from __future__ import annotations

import numpy as np

# Beam's generator constants that are no option of NexmarkConfiguration
HOT_BATCH = 100  # HOT_AUCTION_RATIO / HOT_SELLER_RATIO / HOT_BIDDER_RATIO
ID_LEAD = 10  # AUCTION_ID_LEAD / PERSON_ID_LEAD
FIRST_CATEGORY, CATEGORIES = 10, 5
FIRST_NAMES = (b"Peter", b"Paul", b"Luke", b"John", b"Saul", b"Vicky", b"Kate",
               b"Julie", b"Sarah", b"Deiter", b"Walter")
LAST_NAMES = (b"Shultz", b"Abrams", b"Spencer", b"White", b"Bartels", b"Walton",
              b"Smith", b"Jones", b"Noris")
CITIES = (b"Phoenix", b"Los Angeles", b"San Francisco", b"Boise", b"Portland",
          b"Bend", b"Redmond", b"Seattle", b"Kent", b"Cheyenne")
STATES = (b"AZ", b"CA", b"ID", b"OR", b"WA", b"WY")
EXPIRES_SPAN_MS = 3332
_POOL = 1 << 16  # the letters of one partition
_STARTS = _POOL - 1024  # where a string may start in them: none is longer than 1,024

_BID = b'{"event_type":2,"auction":%d,"bidder":%d,"price":%d,"dateTime":%d,"extra":"'
_AUCTION = (b'{"event_type":1,"id":%d,"itemName":"%s","description":"%s","initialBid":%d,'
            b'"reserve":%d,"dateTime":%d,"expires":%d,"seller":%d,"category":%d,"extra":"')
_PERSON = (b'{"event_type":0,"id":%d,"name":"%s %s","emailAddress":"%s@%s.com",'
           b'"creditCard":"%04d %04d %04d %04d","city":"%s","state":"%s","dateTime":%d,'
           b'"extra":"')
_TAIL = b'"}'


def _extra_len(current: int, average: int, r: int) -> int:
    """Beam's ``nextExtra``: the length that pads ``current`` bytes toward
    ``average``, +-20% of the shortfall."""
    if current > average:
        return 0
    want = average - current
    delta = int(want * 0.2 + 0.5)
    return want - delta + (r % (2 * delta) if delta else 0)


def make_events(
    seed: int, partitions: int, records_per_partition: int,
    only: range | None = None, *,
    person_proportion: int = 1, auction_proportion: int = 3, bid_proportion: int = 46,
    avg_person_bytes: int = 200, avg_auction_bytes: int = 500, avg_bid_bytes: int = 100,
    hot_auction_ratio: int = 2, hot_seller_ratio: int = 4, hot_bidder_ratio: int = 4,
    in_flight_auctions: int = 100, active_people: int = 1000,
    first_id: int = 1000, base_ms: int = 1_700_000_000_000,
) -> dict[int, list[bytes]]:
    """values[p][i] for the partitions in ``only`` (all by default). The
    stream of a partition does not depend on which others are asked for."""
    rpp = records_per_partition
    pp, ap = person_proportion, auction_proportion
    total = pp + ap + bid_proportion
    out = {}
    for p in only if only is not None else range(partitions):
        rng = np.random.default_rng([seed, p])
        n = p * rpp + np.arange(rpp, dtype=np.int64)
        epoch, offset = np.divmod(n, total)
        # lastBase0PersonId / lastBase0AuctionId of every event number
        last_person = epoch * pp + np.minimum(offset, pp - 1)
        before_auctions = offset < pp
        last_auction = np.where(before_auctions, epoch - 1, epoch) * ap + np.where(
            before_auctions | (offset >= pp + ap), ap - 1, offset - pp)
        ri = rng.integers(0, 1 << 31, size=(rpp, 12))
        uf = rng.random((rpp, 4))
        pool = rng.integers(97, 123, size=_POOL, dtype=np.uint8).tobytes()
        # nextBase0AuctionId / nextBase0PersonId, then the hot ones over them
        min_auction = np.maximum(last_auction - in_flight_auctions, 0)
        auction = min_auction + (uf[:, 0] * (last_auction - min_auction + 1 + ID_LEAD)).astype(np.int64)
        hot = ri[:, 0] % hot_auction_ratio > 0
        auction = np.where(hot, last_auction // HOT_BATCH * HOT_BATCH, auction) + first_id
        people = last_person + 1
        active = np.minimum(people, active_people)
        person = people - active + (uf[:, 1] * (active + ID_LEAD)).astype(np.int64)
        bidder = np.where(ri[:, 1] % hot_bidder_ratio > 0,
                          last_person // HOT_BATCH * HOT_BATCH + 1, person) + first_id
        seller = np.where(ri[:, 1] % hot_seller_ratio > 0,
                          last_person // HOT_BATCH * HOT_BATCH, person) + first_id
        price = np.rint(np.power(10.0, uf[:, 2] * 6.0) * 100.0).astype(np.int64)
        price2 = np.rint(np.power(10.0, uf[:, 3] * 6.0) * 100.0).astype(np.int64)
        kind = offset.tolist()
        stamp = (base_ms + n).tolist()
        auction, bidder, seller = auction.tolist(), bidder.tolist(), seller.tolist()
        price, price2 = price.tolist(), price2.tolist()
        own_person = (last_person + first_id).tolist()
        own_auction = (last_auction + first_id).tolist()
        pad_r = ri[:, 3].tolist()
        at = (ri[:, 2] % _STARTS).tolist()
        values = []
        for i in range(rpp):
            k = kind[i]
            if k >= pp + ap:
                head = _BID % (auction[i], bidder[i], price[i], stamp[i])
                average = avg_bid_bytes
            elif k >= pp:
                x = ri[i].tolist()
                name_at, text_at = x[4] % _STARTS, x[5] % _STARTS
                head = _AUCTION % (
                    own_auction[i], pool[name_at : name_at + 3 + x[6] % 17],
                    pool[text_at : text_at + 3 + x[7] % 97], price[i], price[i] + price2[i],
                    stamp[i], stamp[i] + 1 + x[8] % EXPIRES_SPAN_MS, seller[i],
                    FIRST_CATEGORY + x[9] % CATEGORIES)
                average = avg_auction_bytes
            else:
                x = ri[i].tolist()
                user_at, host_at = x[4] % _STARTS, x[5] % _STARTS
                card = x[8]
                head = _PERSON % (
                    own_person[i], FIRST_NAMES[x[6] % len(FIRST_NAMES)],
                    LAST_NAMES[x[7] % len(LAST_NAMES)],
                    pool[user_at : user_at + 3 + x[9] % 4], pool[host_at : host_at + 3 + x[10] % 2],
                    card % 10000, card // 10000 % 10000, x[11] % 10000, x[11] // 10000 % 10000,
                    CITIES[x[0] % len(CITIES)], STATES[x[1] % len(STATES)], stamp[i])
                average = avg_person_bytes
            extra = _extra_len(len(head) + len(_TAIL), average, pad_r[i])
            values.append(head + pool[at[i] : at[i] + extra] + _TAIL)
        out[p] = values
    return out
