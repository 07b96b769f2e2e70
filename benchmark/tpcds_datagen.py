"""TPC-DS store_sales, date_dim, item and store from a seed, in bulk with numpy.

Follows the TPC-DS v3.2 specification, clause 2 (the store-sales star:
columns and types of store_sales 2.3.1... the table definitions) and what
dsdgen makes of it: surrogate keys from 1, date_dim one row a day from
1900-01-02 (d_date_sk 2415022, the Julian day number) to 2100-01-01 whatever
the scale, d_month_seq counted from January 1900, sales between 1998-01-02
and 2003-01-02 heavier from August and heaviest in November and December,
tickets of 8 to 16 lines that share date, time, store and customer, item and
store as slowly changing dimensions (two rows a business key on average),
item's hierarchy of 10 categories, about 100 classes and about 700 brands,
a product name spelt from the digits of the item's key, null foreign keys
and null dimension attributes. Where it departs from dsdgen is listed in
the configuration's file under "assumed".

Imports nothing of the engine. decimal(7,2) columns are float64 rounded to
cents, dates are date32, identifiers bigint, the spec's integers int32:
the types benchmark/scanbytes.py knows.
"""
from __future__ import annotations

import datetime

import numpy as np
import pyarrow as pa

from datagen import write_parquet  # noqa: F401 - a generator's second entry

_EPOCH = datetime.date(1970, 1, 1)
JULIAN_OF_EPOCH = 2440588            # d_date_sk of 1970-01-01
DATE_FIRST = (datetime.date(1900, 1, 2) - _EPOCH).days
DATE_LAST = (datetime.date(2100, 1, 1) - _EPOCH).days
SALES_FIRST = (datetime.date(1998, 1, 2) - _EPOCH).days
SALES_LAST = (datetime.date(2003, 1, 2) - _EPOCH).days

#: dsdgen's row counts at its scale points; between two points the counts are
#: interpolated, below SF1 they shrink with the scale down to a floor
SCALE_POINTS = (1, 10, 100)
ROWS_AT = {"store_sales": (2_880_404, 28_800_991, 287_997_024),
           "item": (18_000, 102_000, 204_000),
           "store": (12, 102, 402)}
FLOOR = {"store_sales": 1, "item": 360, "store": 6}
DATE_DIM_ROWS = DATE_LAST - DATE_FIRST + 1   # 73,049

#: share of a nullable column's rows that are null
NULL_FK = 0.045          # store_sales' foreign keys other than the item
NULL_ATTR = 0.0025       # item's and store's attributes
#: weight of a day of sales by its month (dsdgen's three zones)
MONTH_WEIGHT = (1, 1, 1, 1, 1, 1, 1, 2, 2, 2, 3, 3)

CATEGORIES = ["Books", "Children", "Electronics", "Home", "Jewelry", "Men",
              "Music", "Shoes", "Sports", "Women"]
CLASSES = {
    "Books": ["arts", "business", "computers", "cooking", "entertainments",
              "fiction", "history", "home repair", "mystery", "parenting",
              "reference", "romance", "science", "self-help", "sports",
              "travel"],
    "Children": ["infants", "newborn", "school-uniforms", "toddlers"],
    "Electronics": ["audio", "automotive", "cameras", "camcorders",
                    "disk drives", "dvd/vcr players", "karoke", "memory",
                    "monitors", "musical", "personal", "portable",
                    "scanners", "stereo", "televisions", "wireless"],
    "Home": ["accent", "bathroom", "bedding", "blinds/shades", "curtains/drapes",
             "decor", "flatware", "furniture", "glassware", "kids",
             "lighting", "mattresses", "paint", "rugs", "tables",
             "wallpaper"],
    "Jewelry": ["birdal", "bracelets", "consignment", "costume", "custom",
                "diamonds", "earings", "estate", "gold", "jewelry boxes",
                "loose stones", "mens watch", "pendants", "rings",
                "semi-precious", "womens watch"],
    "Men": ["accessories", "pants", "shirts", "sports-apparel"],
    "Music": ["classical", "country", "pop", "rock"],
    "Shoes": ["athletic", "kids", "mens", "womens"],
    "Sports": ["archery", "athletic shoes", "baseball", "basketball",
               "camping", "fishing", "fitness", "football", "golf",
               "guns", "hockey", "optics", "outdoor", "pools", "sailing",
               "tennis"],
    "Women": ["dresses", "fragrances", "maternity", "swimwear"],
}
BRAND_STEMS = ["amalg", "importo", "edu pack", "exporti", "scholar",
               "brand", "corp", "maxi", "univ", "nameless"]
SYLLABLES = ["bar", "ought", "able", "pri", "ese", "anti", "cally", "ation",
             "eing", "n st"]
SIZES = ["petite", "small", "medium", "large", "extra large", "economy",
         "N/A"]
COLORS = ("almond antique aquamarine azure beige bisque black blanched blue "
          "blush brown burlywood burnished chartreuse chiffon chocolate "
          "coral cornflower cornsilk cream cyan dark deep dim dodger drab "
          "firebrick floral forest frosted gainsboro ghost goldenrod green "
          "grey honeydew hot indian ivory khaki lace lavender lawn lemon "
          "light lime linen magenta maroon medium metallic midnight mint "
          "misty moccasin navajo navy olive orange orchid pale papaya peach "
          "peru pink plum powder puff purple red rose rosy royal saddle "
          "salmon sandy seashell sienna sky slate smoke snow spring steel "
          "tan thistle tomato turquoise violet wheat white yellow").split()
UNITS = ["Unknown", "Each", "Dozen", "Case", "Pallet", "Gross", "Carton",
         "Box", "Bunch", "Bundle", "Oz", "Lb", "Ton", "Ounce", "Pound",
         "Tsp", "Tbl", "Cup", "Dram", "Gram", "N/A"]
WORDS = ("able about above according account act action activities actually "
         "added addition additional administration adults advance advantages "
         "affairs again against agencies ago agreement ahead aid aims air "
         "almost alone already also always american among amounts ancient "
         "animals annual another answers anyway apparent appeal applications "
         "appropriate areas arguments arms army arrangements artists aspects "
         "attempts attitudes authorities available average away back bad "
         "banks bases basic beautiful beds behind benefits best better big "
         "bills black blocks blue boards bodies books boys british broad "
         "brothers buildings businesses calls capital cards careful cars "
         "cases cells central centres certain chairs changes chapters cheap "
         "chief children christian churches circumstances cities civil "
         "claims classes clear clients close clubs cold colleagues colours "
         "commercial committees common communities companies complete").split()
DAY_NAMES = ["Sunday", "Monday", "Tuesday", "Wednesday", "Thursday",
             "Friday", "Saturday"]
STREET_TYPES = ["Street", "Avenue", "Boulevard", "Circle", "Court", "Drive",
                "Lane", "Parkway", "Pkwy", "Road", "Way", "Wy", "Blvd",
                "Ave", "Dr", "Ln", "RD", "ST", "Cir", "Ct"]
STREET_NAMES = ["Main", "Oak", "Park", "Elm", "Maple", "Pine", "Cedar",
                "Lake", "Hill", "Walnut", "Spring", "North", "Ridge",
                "Church", "Willow", "Mill", "Sunset", "Railroad", "Jackson",
                "River", "Highland", "Johnson", "View", "Forest", "Green"]
CITIES = ["Midway", "Fairview", "Oak Grove", "Five Points", "Pleasant Hill",
          "Riverside", "Centerville", "Mount Pleasant", "Bethel",
          "New Hope", "Liberty", "Union", "Oakland", "Salem", "Greenwood"]
COUNTIES = ["Williamson County", "Ziebach County", "Walker County",
            "Daviess County", "Barrow County", "Franklin Parish",
            "Luce County", "Richland County", "Bronx County",
            "Orange County"]
STATES = ["TN", "SD", "AL", "IN", "GA", "LA", "MI", "OH", "NY", "CA"]
NAMES = ["ought", "able", "pri", "ese", "anti", "cally", "ation", "eing",
         "bar", "n st"]
PEOPLE = ["William Ward", "Scott Smith", "Edwin Adams", "David Thomas",
          "Brett Yates", "Raymond Jacobs", "Thomas Pollack", "Ken Harris",
          "Charles Bartley", "Robert Thompson", "Luis Braun", "Mark Hightower"]


def row_counts(sf: float) -> dict:
    """Rows of each table at a scale factor."""
    out = {"date_dim": DATE_DIM_ROWS}
    for name, at in ROWS_AT.items():
        if sf <= SCALE_POINTS[0]:
            n = at[0] * sf
        else:
            hi = next((i for i, p in enumerate(SCALE_POINTS) if sf <= p),
                      len(SCALE_POINTS) - 1)
            lo = hi - 1
            share = (sf - SCALE_POINTS[lo]) / (SCALE_POINTS[hi]
                                               - SCALE_POINTS[lo])
            n = at[lo] + share * (at[hi] - at[lo])
        n = max(int(round(n)), FLOOR[name])
        # the slowly changing dimensions come in runs of six rows
        out[name] = n if name == "store_sales" else -(-n // 6) * 6
    return out


def _strings(values, idx: np.ndarray, null: np.ndarray | None = None):
    """values[idx] as a dictionary column; a value that `values` lists
    twice (a class name two categories share) is one dictionary entry."""
    uniq = list(dict.fromkeys(values))
    where = {v: i for i, v in enumerate(uniq)}
    remap = np.array([where[v] for v in values], np.int32)
    codes = pa.array(remap[idx], mask=null)
    return pa.DictionaryArray.from_arrays(codes, pa.array(uniq, pa.string()))


def _numbers(values: np.ndarray, null: np.ndarray | None = None, kind=None):
    return pa.array(values, type=kind, mask=null)


def _dates(days: np.ndarray, null: np.ndarray | None = None):
    return pa.array(days.astype(np.int32), pa.int32(), mask=null).cast(
        pa.date32())


def _cents(cents: np.ndarray) -> np.ndarray:
    """decimal(7,2) held as the double nearest to it."""
    return cents / 100.0


def _nulls(rng, n: int, share: float) -> np.ndarray:
    return rng.random(n) < share


def _civil(days: np.ndarray) -> tuple:
    """(year, month, day) of days since 1970-01-01."""
    d = days.astype("datetime64[D]")
    y = d.astype("datetime64[Y]")
    m = d.astype("datetime64[M]")
    return (y.astype(np.int64) + 1970, m.astype(np.int64) % 12 + 1,
            (d - m).astype(np.int64) + 1)


def _spell(number: int) -> str:
    """dsdgen's mk_word over a key's decimal digits."""
    return "".join(SYLLABLES[int(c)] for c in str(number))


def _scd(n: int) -> tuple:
    """(business key number, revision, revisions of the key) of each of n
    rows: keys take one, two and three rows in turn, six rows three keys."""
    row = np.arange(n, dtype=np.int64)
    within = row % 6
    key = (row // 6) * 3 + np.array([0, 1, 1, 2, 2, 2])[within]
    rev = np.array([0, 0, 1, 0, 1, 2])[within]
    revs = np.array([1, 2, 2, 3, 3, 3])[within]
    return key, rev, revs


def _scd_dates(rev: np.ndarray, revs: np.ndarray) -> tuple:
    """rec_start_date, rec_end_date and the open rows' mask."""
    starts = np.array([(datetime.date(1997, 10, 27) - _EPOCH).days,
                       (datetime.date(1999, 10, 28) - _EPOCH).days,
                       (datetime.date(2001, 10, 27) - _EPOCH).days])
    first = np.where(revs == 2, np.array([0, 2])[np.minimum(rev, 1)], rev)
    start = starts[np.where(revs == 1, 0, first)]
    nxt = np.where(revs == 2, 2, rev + 1)
    last = rev == revs - 1
    end = starts[np.minimum(nxt, 2)] - 1
    return start, end, last


def date_dim() -> pa.Table:
    days = np.arange(DATE_FIRST, DATE_LAST + 1, dtype=np.int64)
    n = len(days)
    sk = days + JULIAN_OF_EPOCH
    year, month, dom = _civil(days)
    dow = (days + 4) % 7                      # 1970-01-01 was a Thursday
    qoy = (month - 1) // 3 + 1
    month_seq = (year - 1900) * 12 + month - 1
    week_seq = (days - DATE_FIRST + 1) // 7 + 1
    quarter_seq = (year - 1900) * 4 + qoy - 1
    first_dom = sk - dom + 1
    month_len = np.bincount(month_seq)[month_seq]
    holiday = ((month == 1) & (dom == 1)) | ((month == 7) & (dom == 4)) | \
        ((month == 12) & (dom == 25)) | ((month == 11) & (dom == 11))
    yn = ["N", "Y"]
    today = (datetime.date(2003, 1, 8) - _EPOCH).days
    t_year, t_month, _ = _civil(np.array([today]))
    cols = {
        "d_date_sk": _numbers(sk),
        "d_date_id": pa.array([f"AAAAAAAA{k:08d}"[-16:] for k in sk],
                              pa.string()),
        "d_date": _dates(days),
        "d_month_seq": _numbers(month_seq, kind=pa.int32()),
        "d_week_seq": _numbers(week_seq, kind=pa.int32()),
        "d_quarter_seq": _numbers(quarter_seq, kind=pa.int32()),
        "d_year": _numbers(year, kind=pa.int32()),
        "d_dow": _numbers(dow, kind=pa.int32()),
        "d_moy": _numbers(month, kind=pa.int32()),
        "d_dom": _numbers(dom, kind=pa.int32()),
        "d_qoy": _numbers(qoy, kind=pa.int32()),
        "d_fy_year": _numbers(year, kind=pa.int32()),
        "d_fy_quarter_seq": _numbers(quarter_seq, kind=pa.int32()),
        "d_fy_week_seq": _numbers(week_seq, kind=pa.int32()),
        "d_day_name": _strings(DAY_NAMES, dow),
        "d_quarter_name": pa.array(
            [f"{y}Q{q}" for y, q in zip(year, qoy)], pa.string()
        ).dictionary_encode(),
        "d_holiday": _strings(yn, holiday.astype(np.int64)),
        "d_weekend": _strings(yn, ((dow == 0) | (dow == 6)).astype(np.int64)),
        "d_following_holiday": _strings(
            yn, np.r_[False, holiday[:-1]].astype(np.int64)),
        "d_first_dom": _numbers(first_dom, kind=pa.int32()),
        "d_last_dom": _numbers(first_dom + month_len - 1, kind=pa.int32()),
        "d_same_day_ly": _numbers(sk - 365, kind=pa.int32()),
        "d_same_day_lq": _numbers(sk - 91, kind=pa.int32()),
        "d_current_day": _strings(yn, (days == today).astype(np.int64)),
        "d_current_week": _strings(
            yn, (week_seq == week_seq[today - DATE_FIRST]).astype(np.int64)),
        "d_current_month": _strings(
            yn, ((year == t_year) & (month == t_month)).astype(np.int64)),
        "d_current_quarter": _strings(
            yn, (quarter_seq == quarter_seq[today - DATE_FIRST]).astype(
                np.int64)),
        "d_current_year": _strings(yn, (year == t_year).astype(np.int64)),
    }
    assert n == DATE_DIM_ROWS
    return pa.table(cols)


def item(n: int, rng) -> pa.Table:
    key, rev, revs = _scd(n)
    start, end, last = _scd_dates(rev, revs)
    sk = np.arange(1, n + 1, dtype=np.int64)

    def null():
        return _nulls(rng, n, NULL_ATTR)

    category = rng.integers(0, len(CATEGORIES), n)
    per = np.array([len(CLASSES[c]) for c in CATEGORIES])
    offset = np.r_[0, np.cumsum(per)[:-1]]
    class_in = rng.integers(0, 1 << 30, n) % per[category]
    class_names = [c for cat in CATEGORIES for c in CLASSES[cat]]
    class_id = class_in + 1
    # a brand is one of ten stems paired with another, numbered 1 to 10 and
    # tied to the category and class: 71 pairs, 710 brands in use
    pair = (offset[category] + class_in) % 71
    stem_a, stem_b = pair // len(BRAND_STEMS), pair % len(BRAND_STEMS)
    brand_no = rng.integers(1, 11, n)
    brand_pairs = [f"{a}{b} #{k}" for a in BRAND_STEMS for b in BRAND_STEMS
                   for k in range(1, 11)]
    brand_idx = (stem_a * len(BRAND_STEMS) + stem_b) * 10 + brand_no - 1
    brand_id = (category + 1) * 1_000_000 + class_id * 1_000 + brand_no
    manufact = rng.integers(1, 1001, n)
    wholesale = rng.integers(2, 8_800, n)
    price = wholesale + (wholesale * rng.integers(0, 200, n)) // 100 + 1
    desc = rng.integers(0, len(WORDS), (n, 12))
    desc_len = rng.integers(2, 13, n)
    cols = {
        "i_item_sk": _numbers(sk),
        "i_item_id": pa.array([f"AAAAAAAA{k + 1:08d}"[-16:] for k in key],
                              pa.string()),
        "i_rec_start_date": _dates(start, null()),
        "i_rec_end_date": _dates(end, last | null()),
        "i_item_desc": pa.array(
            [" ".join(WORDS[j] for j in desc[i, :desc_len[i]])
             for i in range(n)], pa.string(), mask=null()),
        "i_current_price": _numbers(_cents(price), null()),
        "i_wholesale_cost": _numbers(_cents(wholesale), null()),
        "i_brand_id": _numbers(brand_id, null(), pa.int32()),
        "i_brand": _strings(brand_pairs, brand_idx, null()),
        "i_class_id": _numbers(class_id, null(), pa.int32()),
        "i_class": _strings(class_names, offset[category] + class_in, null()),
        "i_category_id": _numbers(category + 1, null(), pa.int32()),
        "i_category": _strings(CATEGORIES, category, null()),
        "i_manufact_id": _numbers(manufact, null(), pa.int32()),
        "i_manufact": pa.array([_spell(m) for m in manufact], pa.string(),
                               mask=null()).dictionary_encode(),
        "i_size": _strings(SIZES, rng.integers(0, len(SIZES), n), null()),
        "i_formulation": pa.array(
            [f"{a:010d}{COLORS[b]}"[:20] for a, b in zip(
                rng.integers(0, 10**10, n), rng.integers(0, len(COLORS), n))],
            pa.string(), mask=null()),
        "i_color": _strings(COLORS, rng.integers(0, len(COLORS), n), null()),
        "i_units": _strings(UNITS, rng.integers(0, len(UNITS), n), null()),
        "i_container": _strings(["Unknown"], np.zeros(n, np.int64), null()),
        "i_manager_id": _numbers(rng.integers(1, 101, n), null(), pa.int32()),
        "i_product_name": pa.array([_spell(k) for k in sk], pa.string(),
                                   mask=null()),
    }
    return pa.table(cols)


def store(n: int, rng) -> pa.Table:
    key, rev, revs = _scd(n)
    start, end, last = _scd_dates(rev, revs)
    sk = np.arange(1, n + 1, dtype=np.int64)

    def null():
        return _nulls(rng, n, NULL_ATTR)

    def pick(values):
        return _strings(values, rng.integers(0, len(values), n), null())

    closed = rng.random(n) < 0.3
    gmt = rng.integers(5, 9, n)
    cols = {
        "s_store_sk": _numbers(sk),
        "s_store_id": pa.array([f"AAAAAAAA{k + 1:08d}"[-16:] for k in key],
                               pa.string()),
        "s_rec_start_date": _dates(start, null()),
        "s_rec_end_date": _dates(end, last | null()),
        "s_closed_date_sk": _numbers(
            rng.integers(SALES_FIRST, SALES_LAST, n) + JULIAN_OF_EPOCH,
            ~closed),
        "s_store_name": pick(NAMES),
        "s_number_employees": _numbers(rng.integers(200, 301, n), null(),
                                       pa.int32()),
        "s_floor_space": _numbers(rng.integers(5_000_000, 10_000_001, n),
                                  null(), pa.int32()),
        "s_hours": pick(["8AM-4PM", "8AM-8AM", "8AM-12AM"]),
        "s_manager": pick(PEOPLE),
        "s_market_id": _numbers(rng.integers(1, 11, n), null(), pa.int32()),
        "s_geography_class": _strings(["Unknown"], np.zeros(n, np.int64),
                                      null()),
        "s_market_desc": pa.array(
            [" ".join(WORDS[j] for j in row)
             for row in rng.integers(0, len(WORDS), (n, 9))], pa.string(),
            mask=null()),
        "s_market_manager": pick(PEOPLE),
        "s_division_id": _numbers(np.ones(n, np.int64), null(), pa.int32()),
        "s_division_name": _strings(["Unknown"], np.zeros(n, np.int64),
                                    null()),
        "s_company_id": _numbers(np.ones(n, np.int64), null(), pa.int32()),
        "s_company_name": _strings(["Unknown"], np.zeros(n, np.int64),
                                   null()),
        "s_street_number": pa.array(
            [str(k) for k in rng.integers(1, 1000, n)], pa.string(),
            mask=null()),
        "s_street_name": pick(STREET_NAMES),
        "s_street_type": pick(STREET_TYPES),
        "s_suite_number": pa.array(
            [f"Suite {k}" for k in rng.integers(0, 500, n)], pa.string(),
            mask=null()),
        "s_city": pick(CITIES),
        "s_county": pick(COUNTIES),
        "s_state": pick(STATES),
        "s_zip": pa.array([f"{k:05d}" for k in rng.integers(1, 99999, n)],
                          pa.string(), mask=null()),
        "s_country": _strings(["United States"], np.zeros(n, np.int64),
                              null()),
        "s_gmt_offset": _numbers(-gmt.astype(np.float64), null()),
        "s_tax_precentage": _numbers(_cents(rng.integers(0, 12, n)), null()),
    }
    return pa.table(cols)


def _sale_days(rng, n: int) -> np.ndarray:
    """n sale days between SALES_FIRST and SALES_LAST, a day's weight that of
    its month."""
    days = np.arange(SALES_FIRST, SALES_LAST + 1, dtype=np.int64)
    weight = np.array(MONTH_WEIGHT, np.float64)[_civil(days)[1] - 1]
    cum = np.cumsum(weight)
    return days[np.searchsorted(cum, rng.random(n) * cum[-1], side="right")]


def store_sales(n: int, n_item: int, n_store: int, rng) -> pa.Table:
    # tickets of 8 to 16 lines; the last is cut where the table ends
    sizes = rng.integers(8, 17, n // 8 + 1)
    n_ticket = int(np.searchsorted(np.cumsum(sizes), n)) + 1
    sizes = sizes[:n_ticket]
    ticket = np.repeat(np.arange(n_ticket, dtype=np.int64), sizes)[:n]
    line = np.arange(n, dtype=np.int64) - \
        np.repeat(np.cumsum(sizes) - sizes, sizes)[:n]

    def per_ticket(values):
        return values[ticket]

    def fk(values):
        return _numbers(values, _nulls(rng, n, NULL_FK))

    # a ticket's lines take distinct items: a start and a stride a ticket
    start = rng.integers(0, n_item, n_ticket)
    stride = rng.integers(1, max(n_item // 16, 2), n_ticket)
    item_sk = (per_ticket(start) + line * per_ticket(stride)) % n_item + 1

    quantity = rng.integers(1, 101, n)
    wholesale = rng.integers(100, 10_001, n)                  # cents
    lst = wholesale + (wholesale * rng.integers(0, 101, n)) // 100
    sales = (lst * rng.integers(0, 101, n)) // 100
    ext_sales = sales * quantity
    ext_wholesale = wholesale * quantity
    ext_list = lst * quantity
    ext_discount = ext_list - ext_sales
    coupon = np.where(rng.random(n) < 0.2,
                      (ext_sales * rng.integers(0, 101, n)) // 100, 0)
    net_paid = ext_sales - coupon
    tax = (net_paid * rng.integers(0, 10, n)) // 100
    cols = {
        "ss_sold_date_sk": fk(per_ticket(_sale_days(rng, n_ticket))
                              + JULIAN_OF_EPOCH),
        "ss_sold_time_sk": fk(per_ticket(rng.integers(28_800, 75_600,
                                                      n_ticket))),
        "ss_item_sk": _numbers(item_sk),
        "ss_customer_sk": fk(per_ticket(rng.integers(
            1, max(n // 58, 2), n_ticket))),
        "ss_cdemo_sk": fk(per_ticket(rng.integers(1, 1_920_801, n_ticket))),
        "ss_hdemo_sk": fk(per_ticket(rng.integers(1, 7_201, n_ticket))),
        "ss_addr_sk": fk(per_ticket(rng.integers(
            1, max(n // 115, 2), n_ticket))),
        "ss_store_sk": fk(per_ticket(rng.integers(1, n_store + 1, n_ticket))),
        "ss_promo_sk": fk(rng.integers(1, 501, n)),
        "ss_ticket_number": _numbers(ticket + 1),
        "ss_quantity": _numbers(quantity, _nulls(rng, n, NULL_FK),
                                pa.int32()),
    }
    for name, cents in (
            ("ss_wholesale_cost", wholesale), ("ss_list_price", lst),
            ("ss_sales_price", sales), ("ss_ext_discount_amt", ext_discount),
            ("ss_ext_sales_price", ext_sales),
            ("ss_ext_wholesale_cost", ext_wholesale),
            ("ss_ext_list_price", ext_list), ("ss_ext_tax", tax),
            ("ss_coupon_amt", coupon), ("ss_net_paid", net_paid),
            ("ss_net_paid_inc_tax", net_paid + tax),
            ("ss_net_profit", net_paid - ext_wholesale)):
        cols[name] = _numbers(_cents(cents), _nulls(rng, n, NULL_FK))
    return pa.table(cols)


def generate(sf: float, seed: int) -> dict:
    """The four tables of the store-sales star at scale factor `sf`."""
    rows = row_counts(sf)
    streams = np.random.SeedSequence(seed).spawn(3)
    return {
        "store_sales": store_sales(rows["store_sales"], rows["item"],
                                   rows["store"],
                                   np.random.Generator(np.random.PCG64(
                                       streams[0]))),
        "date_dim": date_dim(),
        "item": item(rows["item"],
                     np.random.Generator(np.random.PCG64(streams[1]))),
        "store": store(rows["store"],
                       np.random.Generator(np.random.PCG64(streams[2]))),
    }
