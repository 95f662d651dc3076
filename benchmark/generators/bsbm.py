"""BSBM data (Bizer & Schultz, "The Berlin SPARQL Benchmark", IJSWIS 5(2)
2009; benchmark specification V3.1, section "Data Generation"), from
``--seed``: an e-commerce graph of product types in a hierarchy, product
features, producers, products, vendors, offers, reviewers and reviews.

The one scale parameter is the number of products *n*; everything else
follows by the specification's rules, as recalled (no network here: what is
set in this file and not recalled is listed in the configuration's
``assumed``).

- Product types form a tree of depth ``d = round(log10 n) // 2 + 1`` below
  the root, the root branching ``2 * round(log10 n)``-fold and every other
  type 8-fold.  A type below the root owns a set of product features.
- A product is of one leaf type and carries ``rdf:type`` for that type and
  each of its ancestors, the root too, beside ``rdf:type bsbm:Product``; it
  takes a share of the features its leaf type and the type's ancestors own;
  it has one producer, a label, a comment, three to five textual and three
  to five numeric properties.
- Producers hold about 50 products each and vendors about 2,000 offers
  each (a hundredth of the products in vendors); there are 20 offers and 10
  reviews a product, a reviewer writes about 20 reviews, reviewers and
  reviews belong to rating sites of about 10,000 reviews.
- Producers, vendors and reviewers have a country, drawn from the weighted
  list ``COUNTRIES`` (the United States 40 %).
- Every instance is typed and carries ``dc:publisher`` and ``dc:date``.

Not the Java generator's stream of random numbers, its dictionary of words
or its datatypes: the same shape and about the same size (about 350 triples a
product), not the same file.  Every literal is plain.  The words of a text
are consecutive words of one stream of random words drawn from the seed.

``generate``'s ``scale`` (the rehearsal's override) counts hundreds of
products: ``KOLIBRIE_BENCH_REHEARSAL_SCALE=2`` is 200 products, about 70,000
triples.
"""

import numpy as np

from .watdiv import VOCABULARY  # 4,096 made-up words of 4-7 letters

INST = "http://www4.wiwiss.fu-berlin.de/bizer/bsbm/v01/instances/"
NAMESPACES = {
    "bsbm": "http://www4.wiwiss.fu-berlin.de/bizer/bsbm/v01/vocabulary/",
    "rev": "http://purl.org/stuff/rev#",
    "rdf": "http://www.w3.org/1999/02/22-rdf-syntax-ns#",
    "rdfs": "http://www.w3.org/2000/01/rdf-schema#",
    "dc": "http://purl.org/dc/elements/1.1/",
    "foaf": "http://xmlns.com/foaf/0.1/",
}
COUNTRY_NS = "http://downlode.org/rdf/iso-3166/countries#"
# (ISO code, weight in percent)
COUNTRIES = (("US", 40), ("GB", 10), ("JP", 10), ("CN", 10), ("DE", 5),
             ("FR", 5), ("ES", 5), ("RU", 5), ("KR", 5), ("AT", 5))
PREDICATES = (
    "rdf:type", "rdfs:label", "rdfs:comment", "rdfs:subClassOf",
    "dc:publisher", "dc:date", "dc:title",
    "foaf:homepage", "foaf:name", "foaf:mbox_sha1sum",
    "bsbm:country", "bsbm:producer", "bsbm:productFeature",
    "bsbm:productPropertyTextual1", "bsbm:productPropertyTextual2",
    "bsbm:productPropertyTextual3", "bsbm:productPropertyTextual4",
    "bsbm:productPropertyTextual5",
    "bsbm:productPropertyNumeric1", "bsbm:productPropertyNumeric2",
    "bsbm:productPropertyNumeric3", "bsbm:productPropertyNumeric4",
    "bsbm:productPropertyNumeric5",
    "bsbm:product", "bsbm:vendor", "bsbm:price", "bsbm:validFrom",
    "bsbm:validTo", "bsbm:deliveryDays", "bsbm:offerWebpage",
    "bsbm:reviewFor", "bsbm:reviewDate", "bsbm:rating1", "bsbm:rating2",
    "bsbm:rating3", "bsbm:rating4", "rev:reviewer", "rev:text")
CLASSES = ("ProductType", "ProductFeature", "Producer", "Product", "Vendor",
           "Offer", "Review")
RATIOS = {
    "offers_a_product": 20, "reviews_a_product": 10,
    "products_a_producer": 50.7, "products_a_vendor": 99.8,
    "reviews_a_reviewer": 19.5, "reviews_a_rating_site": 10_000,
    "type_branching_below_the_root": 8,
    "features_a_type": (15, 50), "feature_share_a_product": 0.2,
    "optional_property_shares": {"4": 0.7, "5": 0.8}, "rating_share": 0.7,
}
WORDS = {  # (fewest, most) words of a literal
    "label": (1, 3), "type_comment": (20, 50), "comment": (50, 150),
    "textual_property": (3, 15), "review_title": (4, 15),
    "review_text": (50, 300), "name": (1, 2),
}
_STREAM_WORDS = 4_000_000


def iri(prefixed: str) -> str:
    pre, _, local = prefixed.partition(":")
    return NAMESPACES[pre] + local


def type_tree(n_products: int):
    """``(parent of each type, level of each type)``; type 0 is the root."""
    digits = int(round(np.log10(n_products)))
    depth = digits // 2 + 1
    parent, level, last = [-1], [0], [0]
    for lv in range(1, depth + 1):
        fan = 2 * digits if lv == 1 else RATIOS["type_branching_below_the_root"]
        first = len(parent)
        for node in last:
            parent.extend([node] * fan)
        level.extend([lv] * (len(parent) - first))
        last = range(first, len(parent))
    return np.asarray(parent, np.int64), np.asarray(level, np.int64)


def _group_sizes(rng, total: int, mean: float) -> np.ndarray:
    """``total`` things in groups of about ``mean`` (normal, a third of the
    mean as deviation, at least one a group), summing to ``total``."""
    groups = max(1, int(round(total / mean)))
    weights = np.maximum(rng.normal(mean, mean / 3.0, groups), 1.0)
    bounds = np.rint(np.cumsum(weights) * (total / weights.sum())).astype(np.int64)
    bounds[-1] = total
    return np.maximum(np.diff(np.r_[0, bounds]), 0)


class _Builder:
    """Term table by blocks and triple blocks of ids."""

    def __init__(self, rng):
        self.rng = rng
        self.terms, self.blocks = [], []
        self.pred = {}
        words = np.array(VOCABULARY, dtype=object)[
            rng.integers(0, len(VOCABULARY), _STREAM_WORDS)].tolist()
        self._stream = " ".join(words)
        self._starts = np.cumsum([0] + [len(w) + 1 for w in words])
        self._dates = None

    def add_terms(self, texts) -> np.ndarray:
        start = len(self.terms)
        self.terms.extend(texts)
        return np.arange(start, len(self.terms), dtype=np.int64)

    def iris(self, texts) -> np.ndarray:
        return self.add_terms([f"<{t}>" for t in texts])

    def add(self, s, predicate: str, o) -> None:
        s = np.asarray(s, dtype=np.int64)
        o = np.broadcast_to(np.asarray(o, dtype=np.int64), s.shape)
        self.blocks.append((s, np.full(len(s), self.pred[predicate], np.int64), o))

    def texts(self, kind: str, n: int) -> np.ndarray:
        """``n`` literals of ``WORDS[kind]`` consecutive words of the stream."""
        lo, hi = WORDS[kind]
        lengths = self.rng.integers(lo, hi + 1, n)
        first = self.rng.integers(0, _STREAM_WORDS - hi, n)
        a = self._starts[first].tolist()
        b = (self._starts[first + lengths] - 1).tolist()
        stream = self._stream
        return self.add_terms(['"' + stream[x:y] + '"' for x, y in zip(a, b)])

    def numbers(self, lo: int, hi: int, n: int) -> np.ndarray:
        values = self.rng.integers(lo, hi + 1, n)
        uniq, inverse = np.unique(values, return_inverse=True)
        return self.add_terms([f'"{v}"' for v in uniq.tolist()])[inverse]

    def dates(self, n: int) -> np.ndarray:
        if self._dates is None:
            self._dates = self.add_terms(
                [f'"{y}-{m:02d}-{d:02d}"' for y in range(2000, 2009)
                 for m in range(1, 13) for d in range(1, 29)])
        return self._dates[self.rng.integers(0, len(self._dates), n)]

    def described(self, ids, cls, publisher, comment="comment", label=True):
        """What every instance carries: its class, who published it and when;
        most a label and a comment too."""
        self.add(ids, "rdf:type", cls)
        if label:
            self.add(ids, "rdfs:label", self.texts("label", len(ids)))
            self.add(ids, "rdfs:comment", self.texts(comment, len(ids)))
        self.add(ids, "dc:publisher", publisher)
        self.add(ids, "dc:date", self.dates(len(ids)))


def generate(config: dict, seed: int, scale=None) -> dict:
    """``{"terms", "s", "p", "o", "domains"}``: N-Triples terms, id columns
    and the constants a traffic file may draw."""
    n = int(scale) * 100 if scale else int(config["products"])
    rng = np.random.default_rng([int(seed), 2009])
    b = _Builder(rng)
    b.pred = dict(zip(PREDICATES, b.iris(iri(p) for p in PREDICATES)))
    cls = dict(zip(CLASSES, b.iris(iri("bsbm:" + c) for c in CLASSES)))
    person = int(b.iris([iri("foaf:Person")])[0])
    institution = int(b.iris([INST + "StandardizationInstitution1"])[0])
    codes = [c for c, _ in COUNTRIES]
    country_ids = b.iris(COUNTRY_NS + c for c in codes)
    weights = np.cumsum([w for _, w in COUNTRIES], dtype=np.float64)

    def countries(k):
        return country_ids[np.searchsorted(weights, rng.random(k) * weights[-1],
                                           side="right")]

    # ---- product types and the features each type below the root owns
    parent, level = type_tree(n)
    n_types = len(parent)
    types = b.iris(f"{INST}ProductType{i + 1}" for i in range(n_types))
    b.described(types, cls["ProductType"], institution, "type_comment")
    b.add(types[1:], "rdfs:subClassOf", types[parent[1:]])
    lo, hi = RATIOS["features_a_type"]
    owned = np.r_[0, rng.integers(lo, hi + 1, n_types - 1)]
    feature_start = np.r_[0, np.cumsum(owned)]  # type t owns [start[t], start[t+1])
    n_features = int(feature_start[-1])
    features = b.iris(f"{INST}ProductFeature{i + 1}" for i in range(n_features))
    b.described(features, cls["ProductFeature"], institution, "type_comment")

    # ---- producers and their products
    per_producer = _group_sizes(rng, n, RATIOS["products_a_producer"])
    n_producers = len(per_producer)
    producer_no = np.repeat(np.arange(n_producers), per_producer)
    producers = b.iris(f"{INST}dataFromProducer{k + 1}/Producer{k + 1}"
                       for k in range(n_producers))
    b.described(producers, cls["Producer"], producers)
    b.add(producers, "foaf:homepage",
          b.iris(f"http://www.Producer{k + 1}.com/" for k in range(n_producers)))
    b.add(producers, "bsbm:country", countries(n_producers))
    product_iris = [f"{INST}dataFromProducer{k + 1}/Product{i + 1}"
                    for i, k in enumerate(producer_no.tolist())]
    products = b.iris(product_iris)
    b.described(products, cls["Product"], producers[producer_no])
    b.add(products, "bsbm:producer", producers[producer_no])
    leaves = np.flatnonzero(level == level.max())
    node = leaves[rng.integers(0, len(leaves), n)]
    while (node >= 0).any():  # the leaf type, then each ancestor, the root too
        here = node >= 0
        b.add(products[here], "rdf:type", types[node[here]])
        below_root = here & (node > 0)
        if below_root.any():  # a share of the features this type owns
            who, t = products[below_root], node[below_root]
            k = owned[t]
            feature = np.repeat(feature_start[t], k) + (
                np.arange(int(k.sum())) - np.repeat(np.cumsum(k) - k, k))
            take = rng.random(len(feature)) < RATIOS["feature_share_a_product"]
            b.add(np.repeat(who, k)[take], "bsbm:productFeature",
                  features[feature[take]])
        node = np.where(here, parent[np.maximum(node, 0)], -1)
    for k in "12345":
        share = RATIOS["optional_property_shares"].get(k, 1.0)
        for name, make in (
                ("bsbm:productPropertyTextual" + k,
                 lambda m: b.texts("textual_property", m)),
                ("bsbm:productPropertyNumeric" + k, lambda m: b.numbers(1, 2000, m))):
            who = products if share >= 1.0 else products[rng.random(n) < share]
            b.add(who, name, make(len(who)))

    # ---- vendors and their offers
    n_offers = n * RATIOS["offers_a_product"]
    per_vendor = _group_sizes(
        rng, n_offers, RATIOS["products_a_vendor"] * RATIOS["offers_a_product"])
    n_vendors = len(per_vendor)
    vendor_no = np.repeat(np.arange(n_vendors), per_vendor)
    vendors = b.iris(f"{INST}dataFromVendor{k + 1}/Vendor{k + 1}"
                     for k in range(n_vendors))
    b.described(vendors, cls["Vendor"], vendors)
    b.add(vendors, "foaf:homepage",
          b.iris(f"http://www.vendor{k + 1}.com/" for k in range(n_vendors)))
    b.add(vendors, "bsbm:country", countries(n_vendors))
    offers = b.iris(f"{INST}dataFromVendor{k + 1}/Offer{i + 1}"
                    for i, k in enumerate(vendor_no.tolist()))
    b.described(offers, cls["Offer"], vendors[vendor_no], label=False)
    b.add(offers, "bsbm:product", products[rng.integers(0, n, n_offers)])
    b.add(offers, "bsbm:vendor", vendors[vendor_no])
    cents = rng.integers(500, 1_000_001, n_offers)
    uniq, inverse = np.unique(cents, return_inverse=True)
    b.add(offers, "bsbm:price",
          b.add_terms([f'"{c // 100}.{c % 100:02d}"' for c in uniq.tolist()])[inverse])
    b.add(offers, "bsbm:validFrom", b.dates(n_offers))
    b.add(offers, "bsbm:validTo", b.dates(n_offers))
    b.add(offers, "bsbm:deliveryDays", b.numbers(1, 21, n_offers))
    b.add(offers, "bsbm:offerWebpage",
          b.iris(f"http://www.vendor{k + 1}.com/offers/Offer{i + 1}"
                 for i, k in enumerate(vendor_no.tolist())))

    # ---- rating sites, their reviewers and reviews
    n_reviews = n * RATIOS["reviews_a_product"]
    per_reviewer = _group_sizes(rng, n_reviews, RATIOS["reviews_a_reviewer"])
    n_reviewers = len(per_reviewer)
    reviewer_no = np.repeat(np.arange(n_reviewers), per_reviewer)
    site_of_review = np.arange(n_reviews) // RATIOS["reviews_a_rating_site"]
    first_review = np.cumsum(per_reviewer) - per_reviewer
    site_of_reviewer = site_of_review[np.minimum(first_review, n_reviews - 1)]
    sites = b.iris(f"{INST}dataFromRatingSite{k + 1}/RatingSite{k + 1}"
                   for k in range(int(site_of_review[-1]) + 1))
    reviewers = b.iris(f"{INST}dataFromRatingSite{k + 1}/Reviewer{i + 1}"
                       for i, k in enumerate(site_of_reviewer.tolist()))
    b.described(reviewers, person, sites[site_of_reviewer], label=False)
    b.add(reviewers, "foaf:name", b.texts("name", n_reviewers))
    digest = rng.integers(0, 16, (n_reviewers, 40))
    hexes = np.array(list("0123456789abcdef"), dtype=object)[digest]
    b.add(reviewers, "foaf:mbox_sha1sum",
          b.add_terms(['"' + "".join(row) + '"' for row in hexes.tolist()]))
    b.add(reviewers, "bsbm:country", countries(n_reviewers))
    reviews = b.iris(f"{INST}dataFromRatingSite{k + 1}/Review{i + 1}"
                     for i, k in enumerate(site_of_review.tolist()))
    b.described(reviews, cls["Review"], sites[site_of_review], label=False)
    b.add(reviews, "bsbm:reviewFor", products[rng.integers(0, n, n_reviews)])
    b.add(reviews, "rev:reviewer", reviewers[reviewer_no])
    b.add(reviews, "bsbm:reviewDate", b.dates(n_reviews))
    b.add(reviews, "dc:title", b.texts("review_title", n_reviews))
    b.add(reviews, "rev:text", b.texts("review_text", n_reviews))
    for k in "1234":
        who = reviews[rng.random(n_reviews) < RATIOS["rating_share"]]
        b.add(who, "bsbm:rating" + k, b.numbers(1, 10, len(who)))

    s = np.concatenate([blk[0] for blk in b.blocks])
    p = np.concatenate([blk[1] for blk in b.blocks])
    o = np.concatenate([blk[2] for blk in b.blocks])
    # entity by entity, as the source's file is written: a subject's triples
    # together, so the long texts are spread over the file (by predicate, one
    # block of 100,000 review texts is 120 MB, twice the server's limit a
    # request, and the harness cuts its chunks at blocks)
    order = np.argsort(s, kind="stable")
    s, p, o = s[order], p[order], o[order]
    # one id a distinct term: two literals of one text become one term
    index = {t: i for i, t in enumerate(dict.fromkeys(b.terms))}
    if len(index) < len(b.terms):
        remap = np.fromiter((index[t] for t in b.terms), np.int64, len(b.terms))
        s, p, o = remap[s], remap[p], remap[o]
    pairs = [(a, c) for a in codes for c in codes]  # the ordered pairs
    domains = {
        "product": product_iris,
        "producttype": [f"{INST}ProductType{i + 1}" for i in range(1, n_types)],
        "country1": [COUNTRY_NS + a for a, _ in pairs],
        "country2": [COUNTRY_NS + c for _, c in pairs],
    }
    return {"terms": list(index), "s": s, "p": p, "o": o, "domains": domains}
